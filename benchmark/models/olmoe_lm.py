"""Family ``olmoe_lm``: OLMoE's decoder (``model_type: olmoe``) through
the program's normal model (``horovod_tpu.models.Transformer`` with the
block spec RMSNorm / rotary / QK-norm / dropless top-k experts,
``apply_with_aux`` + ``lm_loss``), the operations one sequence requires,
what the grouped products of a step require, and a plain float32
reference of the same equations.

The reference is written from the equations (arXiv:2409.02060 and the
Hugging Face ``modeling_olmoe``), not from ``horovod_tpu``: ``jax.numpy``
only, precision ``highest``, no kernel, **no sort, no top-k primitive
and no grouped product**: every expert runs on every token, one expert
at a time, and is weighed by the router's probability where the expert
is among the token's k most probable and by 0 elsewhere.  It reads the
program's parameter tree (that layout is the one thing it takes from
the program).  Where the program departs from the published model
(``assumed`` in the configuration file) the reference follows the
program, and the line says so.
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, as ``transformer_lm``
# has it and for its reasons: bfloat16 products (2**-9 a rounding) with
# float32 sums, averaged over >= 4096 tokens; the second bound is on
# the CHANGE of the loss over one optimizer step, relative to itself.
# What is new here is the router: a token whose k-th and (k+1)-th
# probabilities are closer than bfloat16 resolves them chooses another
# expert in the system than in the reference (the system rounds the
# router's INPUT to bfloat16; product and softmax are float32 on both
# sides).  Measured on the v5e at published widths (PERF.md section 6,
# PR 27): 1.1-1.3% of the tokens choose another set of 8; over 20
# seeds the loss is off by 1.2e-4 at most (forward; the limit is 8
# times that).  The reference computed in bfloat16 throughout
# (``perturb="bfloat16"``, the nearest precision below the stated one)
# is off by 5.6e-3 on the forward loss and 1.8e-3 on the group's,
# outside 1e-3, and comes out as not correct: the forward limit is
# what holds the precision.  The update limit holds the optimizer
# (a dropped term, a sum for a mean, a wrong rate move the change by
# tens of percent) and is ResNet's 0.1, not the dense LM's 0.05: the
# first AdamW step at the published 4e-4 takes the repeated sequence's
# loss from 11.5 to 7.3, far outside the linear regime, and there the
# tokens routed otherwise show: over the 20 seeds the change is off by
# 0.65% in the median and by 2.8% at most, a tail that 0.05 would cut
# about once in two hundred seeds (the bfloat16 reference: 0.55%).
TOLERANCE = {"forward": 1e-3, "update": 0.1}
# sequences in the group the update check repeats: the reference's
# forward-backward, float32 AdamW step and forward on ONE sequence are
# 8.1 GiB on the chip, on two 13.9, and four do not fit
CHECK_GROUP = 1
# rows of the head's float32 logits held at once by the reference:
# 2048 x 50304 x 4 bytes = 412 MB where all 16,384 would be 3.3 GB
# beside a float32 Adam step of 625.6 M parameters
LOSS_BLOCK_ROWS = 2048


def _program_config(config):
    from horovod_tpu.models import BlockSpec, TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        d_expert=config["intermediate_size"],
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        max_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        block=BlockSpec(norm="rms", positions="rope", qk_norm=True,
                        ffn="moe_topk"))


def sample_units(config, job):
    """Tokens in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model from ``key``."""
    from horovod_tpu.models import Transformer

    model = Transformer(_program_config(config))
    params = model.init(
        key, jnp.zeros((1, job["seq_len"]), jnp.int32))["params"]
    return params, {}


def make_batch(config, job, key, n):
    """``n`` sequences of uniform random tokens."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: cross-entropy + the job's weights times the
    mean over layers of the load-balancing and router z terms;
    ``(loss, extra)``."""
    from horovod_tpu.models import Transformer, apply_with_aux, lm_loss

    job = config["job"]
    model = Transformer(_program_config(config))
    logits, aux = apply_with_aux(model, params, batch)
    return (lm_loss(logits, batch)
            + job["load_balancing_weight"] * aux["load_balancing"]
            / aux["moe_layers"]
            + job["router_z_weight"] * aux["router_z"]
            / aux["moe_layers"]), extra


def _matmul_params(config):
    """Parameters a token is multiplied with, ``(a layer's, the
    head's)``: attention q, k, v, out; the router; the k experts a
    token visits, three matrices each."""
    d, f = config["hidden_size"], config["intermediate_size"]
    attention = 4 * d * d
    router = d * config["num_experts"]
    experts = config["num_experts_per_tok"] * 3 * d * f
    return attention + router + experts, d * config["vocab_size"]


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), nothing recomputed, matrix
    products only.  Per token ``2 x`` the matmul parameters it meets
    (only the k experts it is routed to: the other 56 are not
    required); causal attention counts the ``T (T + 1) / 2`` query-key
    pairs that are used, twice (scores and the weighted sum), ``2 d``
    operations each, as ``transformer_lm`` does.  Embedding, norms,
    rotary, top-k and the sort are look-ups or below 1%."""
    layers, t = config["num_hidden_layers"], job["seq_len"]
    per_layer, head = _matmul_params(config)
    forward = (2 * (layers * per_layer + head) * t
               + layers * 2 * (2 * config["hidden_size"]) * t * (t + 1) // 2)
    return 3 * forward


def grouped_matmul_flops_per_step(config, job):
    """What ``moe_gmm_roofline`` divides: the operations the grouped
    products of one chip's step require.  A layer has three grouped
    products forward (gate, up, down) and two gradients of each, nine
    in all, each ``2 x rows x 2048 x 1024`` with rows = tokens x
    experts a token: the rows that are routed, and none of any
    padding a kernel may add."""
    rows = (job["per_chip_batch"] * job["seq_len"]
            * config["num_experts_per_tok"])
    return (config["num_hidden_layers"] * 9 * 2 * rows
            * config["hidden_size"] * config["intermediate_size"])


# ------------------------------------------------------------ reference
def _rms_norm(u, w, eps):
    return u / jnp.sqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                        + eps) * w


def _rope(u, theta):
    """``u [T, H, D]``: ``u * cos + rotate_half(u) * sin``."""
    t, _, d = u.shape
    i = jnp.arange(d // 2, dtype=jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (-2 * i / d)
    # the same angles for both halves
    cos, sin = (jnp.concatenate([f(angle)] * 2, -1)[:, None, :].astype(
        u.dtype) for f in (jnp.cos, jnp.sin))
    rotated = jnp.concatenate([-u[..., d // 2:], u[..., :d // 2]], -1)
    return u * cos + rotated * sin


def _attention(x, w, config):
    """One sequence ``x [T, d]``; returns ``x + attention``."""
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    t, d = x.shape
    h = _rms_norm(x, w["ln1"]["scale"], eps)
    # the program's fused projection is [d, 3, heads, head_dim]
    qkv = w["attn"]["qkv"]["kernel"].reshape(d, 3, d)
    # QK-norm over the whole projection, before the split into heads
    q = _rms_norm(h @ qkv[:, 0], w["attn"]["q_norm"]["scale"], eps)
    k = _rms_norm(h @ qkv[:, 1], w["attn"]["k_norm"]["scale"], eps)
    v = h @ qkv[:, 2]
    q, k, v = (u.reshape(t, heads, d // heads) for u in (q, k, v))
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(d // heads)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    mixed = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)
    return x + mixed.reshape(t, d) @ w["attn"]["out"]["kernel"].reshape(d, d)


def _experts(x, w, config, perturb):
    """All tokens ``x [N, d]``; returns ``(x + experts, load-balancing
    term, z term)``."""
    k, n_experts = config["num_experts_per_tok"], config["num_experts"]
    h = _rms_norm(x, w["ln2"]["scale"], config["rms_norm_eps"])
    logits = h @ w["moe"]["router_kernel"]
    p = jax.nn.softmax(logits, -1)
    # an expert is chosen where fewer than k others beat it (a tie goes
    # to the lower index): the k largest, with no sort and no top-k
    beats = (p[:, None, :] > p[:, :, None]) | (
        (p[:, None, :] == p[:, :, None])
        & (jnp.arange(n_experts)[None, :] < jnp.arange(n_experts)[:, None]))
    chosen = jnp.sum(beats, -1) < k                           # [N, E]
    # norm_topk_prob false: the weights are p as they are
    gate = jnp.where(chosen, p, 0.0)
    if perturb == "norm_topk_prob":
        gate = gate / jnp.sum(gate, -1, keepdims=True)

    @jax.checkpoint
    def expert(wg, wu, wd, g):
        return ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd) * g[:, None]

    def add_expert(acc, weights):
        return acc + expert(*weights), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (w["moe"]["wg_kernel"], w["moe"]["wi_kernel"],
         w["moe"]["wo_kernel"], gate.T))
    # f_e: token-slots routed to e over N; P_e: mean probability of e
    f = jnp.mean(chosen.astype(p.dtype), 0)
    load_balancing = n_experts * jnp.sum(f * jnp.mean(p, 0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return x + out, load_balancing, z


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
    """Float32 forward pass and loss; ``(loss, extra)``.  ``perturb``
    names something to get wrong on purpose (tests of the check only):
    ``"norm_topk_prob"`` renormalises the k weights to sum to 1;
    ``"bfloat16"`` computes everything, sums too, in bfloat16, the
    nearest precision below the one the configuration states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    job = config["job"]
    b, t = batch.shape
    with jax.default_matmul_precision("highest"):
        # rotary: no position table
        x = p["embed"]["embedding"][batch]
        terms = []
        for i in range(config["num_hidden_layers"]):
            w = p[f"block_{i}"]
            # attention a sequence at a time: [heads, T, T] scores
            x = jax.lax.map(lambda s: _attention(s, w, config), x)
            # the experts and their two terms over the N tokens of the
            # rank's batch together
            x, *layer_terms = _experts(x.reshape(b * t, -1), w, config,
                                       perturb)
            x = x.reshape(b, t, -1)
            terms.append(layer_terms)
        # the mean over layers of the per-layer terms (Hugging Face
        # takes one term over all layers' tokens; the same at depth 1)
        load_balancing, z = (sum(v) / len(terms) for v in zip(*terms))
        x = _rms_norm(x, p["ln_f"]["scale"], config["rms_norm_eps"])
        # the program's lm_loss: the label of position i is token i + 1
        # and the last position is asked for the FIRST token (a roll)
        labels = jnp.roll(batch, -1, axis=-1)
        # the head is a matrix of its own (tie_word_embeddings false)
        xent = _cross_entropy(x.reshape(b * t, -1), p["lm_head"]["kernel"],
                              labels.reshape(-1))
        return (xent + job["load_balancing_weight"] * load_balancing
                + job["router_z_weight"] * z), extra
