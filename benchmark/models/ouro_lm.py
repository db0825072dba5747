"""Family ``ouro_lm``: a looped language model (``model_type: ouro``,
arXiv:2510.25741) through the program's normal model:
``horovod_tpu.models.Transformer`` with sandwich norms, rotary halves, a
dense SwiGLU, the stack run ``total_ut_steps`` times with one set of
weights, an exit gate, ``apply_with_aux`` + ``looped_lm_loss``.  Beside
it: the operations one sequence requires, what the flash kernels of a
step require, the shapes by which the trace readers find the flash
kernels' and the exits' instructions, and a plain float32 reference of
the same equations.

The reference is written from the equations, not from ``horovod_tpu``:
``jax.numpy`` only, precision ``highest``, no kernel.  With N blocks,
R passes, ``h^(0) = Emb(tokens)``:

    a block:  x <- x + norm_a2(Attn(norm_a1(x)))
              x <- x + norm_m2(SwiGLU(norm_m1(x)))
    a pass:   h^(r) = norm_f(block_N(... block_1(h^(r-1))))
    the gate: lambda^(r)_i = sigmoid(w_g . h^(r)_i + b_g)
    leaving:  p^(r)_i = lambda^(r)_i prod_{s<r} (1 - lambda^(s)_i),
              the last pass takes what is left
    the loss: mean_i [ sum_r p^(r)_i l^(r)_i - beta H(p_i) ],
              l^(r)_i = xent(W_head h^(r)_i, t_{i+1})

It is computed in blocks so that it fits beside a float32 AdamW step:
the passes are a ``jax.lax.scan`` (one set of weights, R times), a block
application and a head of attention under ``jax.checkpoint``, the logits
in blocks of rows.  It reads the program's parameter tree (that layout
is the one thing it takes from the program).
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, and on the CHANGE of the
# loss over one optimizer step relative to itself: bfloat16 products
# with float32 sums against float32 at ``highest``.  Each limit lies
# between two readings on the v5e at published widths (PERF.md section
# 6, PR 33).  Forward: over 33 seeds the system is off by at most
# 5.0e-5 on the forward loss and 5.5e-5 on the group's; the reference
# computed in bfloat16 throughout (``perturb="bfloat16"``, the nearest
# precision below the stated one; its loss itself has steps of 0.0625
# at 11.2, so one of its two readings can come out small by chance) is
# off on the larger of the two by 3.8e-4 at least over 24 seeds (median
# 1.6e-3) and comes out as not correct by this limit in every one.
# Update, at the job's rate of 1e-6 (the configuration's
# ``optimizer_tried`` says why not the paper's peak rate: there the
# first step is outside the regime in which the change follows from the
# gradient, and the same comparison reads up to 1.54): the first AdamW
# step takes the repeated sequence's loss down by 0.044-0.060 of 11.2
# and the system is off by at most 1.1e-2 of that over 15 seeds (median
# 4.7e-3); the reference in bfloat16 reads 4.4e-2 to 1.0 over 8.
TOLERANCE = {"forward": 1.5e-4, "update": 0.03}
# sequences in the group the update check repeats: the reference's
# forward-backward, float32 AdamW step and forward of 612 M parameters
CHECK_GROUP = 1
# rows of the head's float32 logits held at once by the reference
LOSS_BLOCK_ROWS = 1024


def _program_config(config):
    from horovod_tpu.models import BlockSpec, TransformerConfig

    assert config["hidden_act"] == "silu" and config["rope_scaling"] is None
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    assert not config["tie_word_embeddings"]
    assert set(config["layer_types"]) == {"full_attention"}
    assert not config["use_sliding_window"]
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        remat=config["remat"],
        passes=config["total_ut_steps"],
        exit_gate=True,
        block=BlockSpec(norm="rms", positions="rope", ffn="swiglu",
                        norm_placement="sandwich"))


def _model(config):
    from horovod_tpu.models import Transformer

    return Transformer(_program_config(config))


def sample_units(config, job):
    """Tokens in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model from ``key``;
    ``extra`` carries the last step's two counters a pass (zeros)."""
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    passes = jnp.zeros((config["total_ut_steps"],), jnp.float32)
    return _model(config).init(key, tokens)["params"], {
        "exit_probability": passes, "exit_losses": passes}


def make_batch(config, job, key, n):
    """``n`` sequences of uniform random tokens."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: the expectation of the exits' cross-entropies
    over the exit distribution less the job's ``beta`` times its
    entropy; ``(loss, extra)`` with the step's counters."""
    from horovod_tpu.models import apply_with_aux, looped_lm_loss

    logits, aux = apply_with_aux(_model(config), params, batch)
    total, exits = looped_lm_loss(logits, aux["exit_gate_logits"], batch,
                                  config["job"]["beta"])
    return total, {name: exits[name] for name in extra}


def _block_params(config):
    """Matmul parameters of one block: q, k, v and the output
    projection, gate, up and down."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"] * config["head_dim"]
    return 4 * d * heads + 3 * d * config["intermediate_size"]


def _causal_attention_flops(config, batch, t):
    """Forward operations of causal attention in every application of
    every block: the ``T (T + 1) / 2`` query-key pairs that are used,
    ``2 head_dim`` for the score and ``2 head_dim`` for the weighted sum
    each."""
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    return (applications * batch * config["num_attention_heads"]
            * 4 * config["head_dim"] * t * (t + 1) // 2)


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), **nothing recomputed**, matrix
    products only: per token ``2 x`` the matmul parameters it meets (a
    block's ``total_ut_steps`` times; the head and the gate once an
    exit), and causal attention as counted above.  Norms, rotary and
    the loss are below 1%."""
    t, passes = job["seq_len"], config["total_ut_steps"]
    d = config["hidden_size"]
    per_token = passes * (
        config["num_hidden_layers"] * _block_params(config)
        + d * config["vocab_size"] + d)
    return 3 * (2 * per_token * t + _causal_attention_flops(config, 1, t))


def flash_flops_per_step(config, job):
    """What ``flash_roofline`` divides: the operations the flash kernels
    of one chip's step require, forward and both gradients (3 x
    forward), the causal pairs only, nothing recomputed."""
    return 3 * _causal_attention_flops(config, job["per_chip_batch"],
                                       job["seq_len"])


def trace_shapes(config, job):
    """The shapes by which ``loop_trace.py`` finds instructions in a
    device trace, as they stand in an instruction's text (PERF.md
    section 3; read off the compiled step).  ``flash``: q and k of the
    three flash custom calls, ``[batch x heads, T, head_dim]``.
    ``exits``: the logits of the exits, ``[rows, V]`` with rows = passes
    x batch x T (the one head runs on all exits at once), and as the
    model shapes them, ``[passes, batch, T, V]``, with and without the
    axes of extent 1 (the compiler drops them)."""
    b, t = job["per_chip_batch"], job["seq_len"]
    passes, vocab = config["total_ut_steps"], config["vocab_size"]

    def shape(*dims):
        return "[" + ",".join(str(d) for d in dims) + "]"

    whole = (passes, b, t, vocab)
    return {
        "flash": [shape(b * config["num_attention_heads"], t,
                        config["head_dim"])],
        "exits": sorted({shape(passes * b * t, vocab), shape(*whole),
                         shape(*(d for d in whole if d != 1))}),
    }


# ------------------------------------------------------------ reference
def _rms_norm(u, w, eps):
    return u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * w


def _rotate(u, theta):
    """``u [H, T, D]``: column i < D / 2 and column i + D / 2 at position
    t are one pair, turned by ``t * theta^(-2i / D)``."""
    _, t, d = u.shape
    half = d // 2
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             / theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angle).astype(u.dtype), jnp.sin(angle).astype(u.dtype)
    lo, hi = u[..., :half], u[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(h, w, config):
    """Causal softmax attention of one normed sequence ``h [T, d]``, a
    head at a time; no biases."""
    heads, dim = config["num_attention_heads"], config["head_dim"]
    t, d = h.shape
    theta = float(config["rope_theta"])
    # the program's fused projection is [d, 3, heads, head_dim]
    q, k, v = jnp.einsum("td,dchk->chtk", h, w["qkv"]["kernel"])
    q, k = _rotate(q, theta), _rotate(k, theta)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args
        scores = jnp.where(causal, q_h @ k_h.T / math.sqrt(dim), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v_h

    mixed = jax.lax.map(head, (q, k, v))                     # [H, T, D]
    return jnp.einsum("htk,hkd->td", mixed,
                      w["out"]["kernel"].reshape(heads, dim, d))


def _block(x, w, config):
    """One block on one sequence ``x [T, d]``: a norm before and a norm
    after each branch."""
    eps = config["rms_norm_eps"]
    x = x + _rms_norm(
        _attention(_rms_norm(x, w["ln1"]["scale"], eps), w["attn"], config),
        w["ln1_post"]["scale"], eps)
    h = _rms_norm(x, w["ln2"]["scale"], eps)
    m = w["mlp"]
    h = ((jax.nn.silu(h @ m["gate"]["kernel"]) * (h @ m["up"]["kernel"]))
         @ m["down"]["kernel"])
    return x + _rms_norm(h, w["ln2_post"]["scale"], eps)


def _token_losses(x, head, labels):
    """``-log softmax(x head)[label]`` of every row of ``x``, the logits
    made a block of rows at a time and made again in the backward
    pass."""
    rows = x.shape[0]
    block = math.gcd(rows, LOSS_BLOCK_ROWS)

    @jax.checkpoint
    def block_losses(args):
        xs, ys = args
        logits = xs @ head
        picked = jnp.take_along_axis(logits, ys[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    return jax.lax.map(block_losses, (
        x.reshape(rows // block, block, -1),
        labels.reshape(rows // block, block))).reshape(rows)


def reference_loss(config, params, extra, batch, perturb=None):
    """Float32 forward pass and loss; ``(loss, extra)`` with the two
    counters.  ``perturb`` names something to get wrong on purpose
    (tests of the check only): ``"beta"`` leaves the entropy's term out;
    ``"total_ut_steps"`` runs one pass fewer; ``"bfloat16"`` computes
    everything, sums too, in bfloat16, the nearest precision below the
    one the configuration states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    passes = config["total_ut_steps"] - (perturb == "total_ut_steps")
    beta = 0.0 if perturb == "beta" else config["job"]["beta"]
    b, t = batch.shape
    # the program's labels: the label of position i is token i + 1 and
    # the last position is asked for the FIRST token (a roll)
    labels = jnp.roll(batch, -1, axis=-1).reshape(-1)
    block = jax.checkpoint(lambda x, w: jax.vmap(
        lambda s: _block(s, w, config))(x))

    def one_pass(h, _):
        # ONE set of weights, whatever the pass
        for i in range(config["num_hidden_layers"]):
            h = block(h, p[f"block_{i}"])
        # the final norm closes the pass: this pass's exit and the
        # next pass's input
        h = _rms_norm(h, p["ln_f"]["scale"], config["rms_norm_eps"])
        rows = h.reshape(b * t, -1)
        gate = p["exit_gate"]
        leave = jax.nn.sigmoid(rows @ gate["kernel"][:, 0] + gate["bias"][0])
        # the head is a matrix of its own (tie_word_embeddings false)
        return h, (leave, _token_losses(rows, p["lm_head"]["kernel"],
                                        labels))

    with jax.default_matmul_precision("highest"):
        # rotary: no position table
        _, (leave, losses) = jax.lax.scan(
            one_pass, p["embed"]["embedding"][batch], None, length=passes)
        # p^(r): leaves at r having stayed at every s < r; the last pass
        # takes what is left, its own gate is not asked
        stayed, probability = jnp.ones_like(leave[0]), []
        for r in range(passes - 1):
            probability.append(leave[r] * stayed)
            stayed = stayed * (1 - leave[r])
        probability = jnp.stack(probability + [stayed])    # [R, rows]
        entropy = -jnp.sum(probability * jnp.log(probability), 0)
        total = jnp.mean(jnp.sum(probability * losses, 0) - beta * entropy)
    return total, {
        "exit_probability": jnp.mean(probability, 1).astype(jnp.float32),
        "exit_losses": jnp.mean(losses, 1).astype(jnp.float32)}
