"""Family ``evabyte_lm``: a byte-level LM whose attention is softmax
attention linearised by chunk (``model_type: evabyte``, ``attention_class:
eva``; EVA, arXiv:2302.04542) through the program's normal model:
``horovod_tpu.models.Transformer`` with ``ChunkSummaryAttention`` in every
block, RMSNorm with a unit offset, SwiGLU, a residual stream carried in
float32, and a head that predicts ``num_pred_heads`` bytes a position
(``multi_offset_lm_loss``).  Beside it: the operations one sequence
requires, what the attention kernels of a step require, the bytes a
step's pooling has to move, and a plain float32 reference of the same
equations.

The reference is written from the equations, not from ``horovod_tpu``:
``jax.numpy`` only, precision ``highest``, no kernel.  With ``u =
norm(x)``, ``d`` = 4096, ``H`` = 32 heads of ``D`` = 128, ``W`` = 2048,
``C`` = 16, ``s = D^-1/2``:

    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)
    q, k, v = u W_q, u W_k, u W_v;   q, k turned by RoPE (halves layout)
    pooling, head h, chunk c (positions 16c .. 16c + 15), on the turned k:
        p = softmax_j(s k_j . phi_h);  kt_c = sum_j p_j k_j + mu_h
                                       vt_c = sum_j p_j v_j
    attention, query i in window w = i // W: the keys 2048 w <= j <= i and
        the summaries c < 128 w, ONE softmax over the joined scores
        s q_i . k_j and s q_i . kt_c;  o_i = sum alpha v_j + sum beta vt_c
    x = x + concat_h(o) W_o;   x = x + (silu(n W_g) * (n W_u)) W_d, n = norm(x)
    logits = norm_f(x) W_head  [8 heads of 320];  head r at t is asked for
        token t + 1 + r;  loss = mean over positions and heads

each head's scores a dense matrix over the joined keys ``[T + T / 16]``
under a mask made from indices, the pooling a reshape to ``[T / 16, 16,
D]`` and a softmax, the loss eight shifted cross-entropies.  It is
computed in blocks so that it fits beside a float32 AdamW step at the
cell's length: a layer at a time under ``jax.checkpoint``, a head of
attention at a time (its projections too) and a block of its query rows
at a time, the feed-forward a block of rows at a time.  It reads the
program's parameter tree (that layout is the one thing it takes from the
program).

Departures from the release, each under ``assumed`` in the
configuration's file: the four details of the attention written from
memory of ``eva.py`` (the pooling weights, where ``mu`` enters, that a
summary becomes visible when its WINDOW is complete, the equal weights of
the eight heads' losses), RoPE's layout, the seeded start, and the
roll of the labels (the last ``1 + r`` positions of head ``r`` are asked
for the first tokens, as the program's ``lm_loss`` has it).  The pooling
has no matrix product and is not among the required operations.
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, and on the CHANGE of the
# loss over one optimizer step relative to itself: bfloat16 products with
# float32 sums and a float32 residual stream against float32 at
# ``highest``.  Read on the v5e at the cell's own load, 1 x 16384 (PERF.md
# section 6, PR 49, seeds 4900000001, 4900000101-112, 4900000201-206 and
# 4900000301, the ranges here).
#
# ``forward`` (it holds both losses): over 20 seeds and 40 readings the
# system is off by 3.8e-7 to 3.5e-5.  The reference computed in bfloat16
# throughout (``perturb_reference="bfloat16"``, the nearest precision
# below the stated one), the larger of its two losses a seed, 12 seeds:
# 5.7e-4 to 4.1e-3 (its losses have steps of 0.03125 and read 6.25 or
# 6.28125 where the cell's are 6.244 to 6.288).  The limit lies between
# the two readings, 3.7 times over the system's largest and 4.4 times
# under the control's smallest: the control came out as not correct in 12
# seeds of 12.
#
# ``update``: the system 6.9e-5 to 1.24e-3, the control 2.7e-3 to 0.25:
# apart in these seeds, but by 2.2 times only, so this limit is not set
# between them: it holds the cell against a state left unchanged, which
# reads 1, and against a step of another size, with 8 times of room over
# the system's largest reading, since fresh seeds read higher.
#
# What neither loss can see at four layers: the program with a bfloat16
# residual stream reads forward 0 to 4.3e-5 and update 4.4e-5 to 8.0e-4
# in the same 12 seeds, inside the system's own ranges.  What parts them
# is the CPU tests (a block's output dtype, and the stream rounded in the
# reference at toy sizes, ``tests/test_transformer_chunk_summary.py``,
# ``tests/benchmark/test_benchmark_evabyte_reference.py``) and the
# compiled step's text (``f32[1,16384,4096]`` between the blocks,
# ``tests/test_chip_compile.py``).
TOLERANCE = {"forward": 1.3e-4, "update": 0.01}
# sequences in the group the update check repeats
CHECK_GROUP = 1
# query rows of one head's dense scores held at once by the reference, and
# rows of the feed-forward's float32 arrays
SCORE_BLOCK_ROWS = 1024
MLP_BLOCK_ROWS = 2048


def _head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def _program_config(config):
    from horovod_tpu.models import (BlockSpec, ChunkSummaryAttention, Rotary,
                                    TransformerConfig)

    assert config["attention_class"] == "eva"
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert not config["tie_word_embeddings"]
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    assert config["rope_scaling"] is None
    assert config["norm_add_unit_offset"] and config["fp32_skip_add"]
    assert config["fp32_logits"] and config["mixedp_attn"]
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        residual_dtype=jnp.dtype(config["residual_dtype"]),
        logits_dtype=jnp.float32, norm_unit_offset=True,
        head_outputs=config["num_pred_heads"], remat=config["remat"],
        block=BlockSpec(
            norm="rms", positions="rope", ffn="swiglu",
            attention=ChunkSummaryAttention(
                heads=config["num_attention_heads"],
                head_dim=_head_dim(config), window=config["window_size"],
                chunk=config["chunk_size"],
                rotary=Rotary(theta=float(config["rope_theta"])))))


def _model(config):
    from horovod_tpu.models import Transformer

    return Transformer(_program_config(config))


def sample_units(config, job):
    """Tokens (bytes) in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model from ``key``; there is
    no state beside the parameters."""
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    return _model(config).init(key, tokens)["params"], {}


def make_batch(config, job, key, n):
    """``n`` sequences of byte-tokens uniform over the vocabulary."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: the mean cross-entropy of the
    ``num_pred_heads`` outputs a position, output ``r`` for token ``t +
    1 + r``."""
    from horovod_tpu.models import multi_offset_lm_loss

    logits = _model(config).apply({"params": params}, batch)
    return multi_offset_lm_loss(logits, batch,
                                config["num_pred_heads"]), extra


def _matmul_params(config):
    """Parameters a token is multiplied with: ``(a layer's, the
    head's)``.  The norms' scales and the attention's two learned
    vectors a head are none; the embedding's lookup is no product."""
    d = config["hidden_size"]
    layer = 4 * d * d + 3 * d * config["intermediate_size"]
    return layer, d * config["num_pred_heads"] * config["vocab_size"]


def allowed_pairs(t, window, chunk):
    """``(local, remote)``: the query-key pairs of one head on a sequence
    of ``t``: a query and the positions of its own window up to itself,
    and a query and the summaries of every earlier window (``window /
    chunk`` a window)."""
    windows, rest = divmod(t, window)
    local = windows * window * (window + 1) // 2 + rest * (rest + 1) // 2
    # window w's queries (the last, partial one too) see w whole windows
    # of summaries
    remote = (window // chunk) * (
        window * windows * (windows - 1) // 2 + rest * windows)
    return local, remote


def _attention_flops(config, batch, t):
    """Forward operations of attention in every layer: the allowed pairs
    alone, local and remote, ``2 D`` for a score and ``2 D`` for the
    weighted sum a pair and head."""
    pairs = sum(allowed_pairs(t, config["window_size"],
                              config["chunk_size"]))
    return (batch * config["num_hidden_layers"]
            * config["num_attention_heads"] * 4 * _head_dim(config) * pairs)


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), nothing recomputed, matrix
    products only: per token ``2 x`` the matmul parameters it meets, and
    attention over the allowed pairs, local and remote.  The pooling is
    no matrix product (sixteen positions weighed into one: ``4 D``
    multiply-adds a position and head, 0.03% of a layer's products) and
    is left out; it shows as time."""
    t = job["seq_len"]
    layer, head = _matmul_params(config)
    per_token = config["num_hidden_layers"] * layer + head
    return 3 * (2 * per_token * t + _attention_flops(config, 1, t))


def eva_flash_flops_per_step(config, job):
    """What ``eva_flash_roofline`` divides: the operations the attention
    kernels of one chip's step require, forward and both gradients (3 x
    forward), the allowed pairs alone, local and remote, nothing
    recomputed; the same count whether one kernel or two compute it."""
    return 3 * _attention_flops(config, job["per_chip_batch"],
                                job["seq_len"])


def eva_pool_bytes_per_step(config, job):
    """What ``eva_pool_roofline`` divides: the bytes ANY implementation
    of the pooling must move a step at the activation dtype, nothing
    recomputed.  With ``N`` the numbers of k (``B T H D``): the forward
    reads k and v and writes kt and vt (``2 N + 2 N / chunk``), the
    backward reads k, v and the two cotangents and writes dk and dv (``4
    N + 2 N / chunk``)."""
    n = (job["per_chip_batch"] * job["seq_len"]
         * config["num_attention_heads"] * _head_dim(config))
    numbers = 6 * n + 4 * n // config["chunk_size"]
    return (numbers * jnp.dtype(config["activation_dtype"]).itemsize
            * config["num_hidden_layers"])


# ------------------------------------------------------------ reference
def _norm(x, w, eps, perturb):
    offset = 0.0 if perturb == "norm_without_offset" else 1.0
    return (x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * (offset + w["scale"]))


def _rope(x, theta):
    """``x [T, D]`` (one head) turned in the halves layout: column ``i``
    pairs with ``i + D / 2``, the angle ``t theta^(-2i / D)``."""
    t, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _pool(k, v, phi, mu, chunk, perturb):
    """The summaries ``[T / chunk, D]`` of one head's k, v ``[T, D]``: a
    reshape to chunks and a softmax over a chunk's positions."""
    t, d = k.shape
    kc, vc = (u.reshape(t // chunk, chunk, d) for u in (k, v))
    p = jax.nn.softmax(kc @ phi / math.sqrt(d), axis=1)[..., None]
    kt, vt = jnp.sum(p * kc, 1), jnp.sum(p * vc, 1)
    if perturb == "mu_on_values":
        return kt, vt + mu
    return kt + mu, vt


def _head(u, w_q, w_k, w_v, phi, mu, config, perturb):
    """One head of one sequence: ``u [T, d]`` -> ``[T, D]``.  The scores
    a block of query rows at a time, a dense softmax over the joined keys
    (the ``T`` positions, then the ``T / chunk`` summaries) under a mask
    made from indices."""
    t, d = u.shape[0], w_q.shape[-1]
    window, chunk = config["window_size"], config["chunk_size"]
    theta = config["rope_theta"]
    q, k, v = _rope(u @ w_q, theta), _rope(u @ w_k, theta), u @ w_v
    kt, vt = _pool(k, v, phi, mu, chunk, perturb)
    keys, values = jnp.concatenate([k, kt]), jnp.concatenate([v, vt])
    rows = math.gcd(t, SCORE_BLOCK_ROWS)
    j, c = jnp.arange(t)[None, :], jnp.arange(t // chunk)[None, :]

    @jax.checkpoint
    def block(args):
        q_b, first = args
        i = first + jnp.arange(rows)[:, None]
        start = i // window * window
        if perturb == "visible_by_chunk":
            # a summary seen as soon as its own chunk is complete
            seen = (c + 1) * chunk <= i
        else:
            seen = c < start // chunk
        allowed = jnp.concatenate([(j >= start) & (j <= i), seen], axis=1)
        scores = jnp.where(allowed, q_b @ keys.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ values

    blocks = t // rows
    return jax.lax.map(block, (q.reshape(blocks, rows, d),
                               jnp.arange(blocks) * rows)).reshape(t, d)


def _attention(u, a, config, perturb):
    """The mixer on one normed sequence ``u [T, d]``: a head at a time
    (its projections, rotation, pooling and softmax, made again in the
    backward pass), the heads joined and projected.  The program's q, k
    and v kernels are ``[d, H, D]``, ``phi`` and ``mu`` ``[H, D]``."""
    heads = jax.lax.map(
        jax.checkpoint(lambda w: _head(u, *w, config, perturb)),
        tuple(a[name]["kernel"].transpose(1, 0, 2)
              for name in ("q", "k", "v")) + (a["phi"], a["mu"]))
    o = heads.transpose(1, 0, 2).reshape(u.shape[0], -1)      # [T, H D]
    return o @ a["out"]["kernel"].reshape(o.shape[-1], -1)


def _swiglu(n, w):
    """The feed-forward on ``n [T, d]``, a block of rows at a time."""
    t = n.shape[0]
    rows = math.gcd(t, MLP_BLOCK_ROWS)

    @jax.checkpoint
    def block(n_b):
        return ((jax.nn.silu(n_b @ w["gate"]["kernel"])
                 * (n_b @ w["up"]["kernel"])) @ w["down"]["kernel"])

    return jax.lax.map(block, n.reshape(t // rows, rows, -1)).reshape(t, -1)


def _block(x, w, config, perturb):
    """One block on one sequence ``x [T, d]``."""
    eps = config["rms_norm_eps"]

    def stream(x):
        if perturb == "residual_bfloat16":
            return x.astype(jnp.bfloat16).astype(x.dtype)
        return x

    x = stream(x + _attention(_norm(x, w["ln1"], eps, perturb), w["attn"],
                              config, perturb))
    return stream(x + _swiglu(_norm(x, w["ln2"], eps, perturb), w["mlp"]))


def _shifted_cross_entropy(x, head, tokens, outputs):
    """Mean over positions and outputs of ``-log softmax(x W)[label]``:
    ``outputs`` cross-entropies over the vocabulary's columns, output
    ``r`` at position ``t`` asked for token ``t + 1 + r`` (a roll)."""
    t = x.shape[0]
    logits = (x @ head).reshape(t, outputs, -1)
    total = 0.0
    for r in range(outputs):
        logp = jax.nn.log_softmax(logits[:, r], -1)
        labels = jnp.roll(tokens, -(1 + r))
        total = total - jnp.mean(
            jnp.take_along_axis(logp, labels[:, None], -1))
    return total / outputs


def reference_loss(config, params, extra, batch, perturb=None):
    """Float32 forward pass and loss; ``(loss, extra)``.  ``perturb``
    names something to get wrong on purpose (tests of the check only):
    ``"visible_by_chunk"`` shows a summary as soon as its chunk is
    complete, not its window; ``"mu_on_values"`` adds ``mu`` to the
    pooled values, not the keys; ``"norm_without_offset"`` multiplies by
    ``g``, not ``1 + g``; ``"residual_bfloat16"`` rounds the residual
    stream to bfloat16 after every sum; ``"bfloat16"`` computes
    everything, sums too, in bfloat16, the nearest precision below the
    one the configuration states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    outputs = config["num_pred_heads"]

    def sequence(tokens):
        x = p["embed"]["embedding"][tokens]
        for i in range(config["num_hidden_layers"]):
            x = jax.checkpoint(
                lambda x, w: _block(x, w, config, perturb))(
                    x, p[f"block_{i}"])
        x = _norm(x, p["ln_f"], config["rms_norm_eps"], perturb)
        return _shifted_cross_entropy(x, p["lm_head"]["kernel"], tokens,
                                      outputs)

    with jax.default_matmul_precision("highest"):
        total = jnp.mean(jax.lax.map(sequence, batch))
    return total.astype(jnp.float32), extra
