"""Family ``transformer_lm``: the program's GPT-2-style decoder
(``horovod_tpu.models.Transformer`` + ``lm_loss``) built from a
configuration file, the operations one sequence requires, and a plain
float32 reference of the same block.

The reference is written from the equations, not from
``horovod_tpu.models``: ``jax.numpy`` only, no kernels, layers under
``lax.scan``.  It reads the program's parameter tree (that layout is
the one thing it takes from the program).  It implements the block *as
the program has it*; where that departs from the published GPT-2
(``assumed`` in the configuration file) the line says so.
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss.  The system runs its
# matmuls and activations in bfloat16 (8 bits of mantissa, 2**-9 a
# rounding) with float32 sums; errors of thousands of roundings average
# out in a mean over >= 1024 tokens.  Measured on the v5e (PERF.md,
# PR 22): forward 5e-5, after one update 4e-5 of the loss.  The second
# bound is on the CHANGE of the loss over one optimizer step, relative
# to that change: a dropped term, a sum in place of a mean under SGD or
# a wrong rate moves it by tens of percent.
TOLERANCE = {"forward": 1e-3, "update": 0.05}
# sequences in the group the update check repeats
CHECK_GROUP = 1


def _program_config(config):
    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"], n_layers=config["n_layer"],
        d_model=config["n_embd"], n_heads=config["n_head"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=jnp.dtype(config["activation_dtype"]))


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
    """The program's loss: ``(loss, extra)``."""
    from horovod_tpu.models import Transformer, lm_loss

    model = Transformer(_program_config(config))
    return lm_loss(model.apply({"params": params}, batch), batch), extra


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward: one product for the input's
    gradient, one for the weight's), nothing recomputed.

    Matrix products only (the rest is below 1%): per token
    ``2 * (12 d^2 L + d V)`` forward with ``n_inner = 4 d``; causal
    attention counts the ``T (T + 1) / 2`` query-key pairs that are
    used, twice (scores and the weighted sum), ``2 d`` operations
    each.  The embeddings are look-ups."""
    d, layers = config["n_embd"], config["n_layer"]
    t = job["seq_len"]
    per_layer = 4 * d * d + 2 * d * config["n_inner"]
    matmul_params = layers * per_layer + d * config["vocab_size"]
    forward = 2 * matmul_params * t + layers * 2 * (2 * d) * t * (t + 1) // 2
    return 3 * forward


# ------------------------------------------------------------ reference
def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(
        math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_loss(config, params, extra, batch, perturb=None):
    """Float32 forward pass and loss; ``(loss, extra)``.  ``perturb``
    names a term to get wrong on purpose (tests of the check only)."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    p = f32(params)
    layers = config["n_layer"]
    # the program's LayerNorm uses 1e-6; published GPT-2 has 1e-5
    eps = config["layer_norm_epsilon"]
    t = batch.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, w):
        y = _layer_norm(x, w["ln1"], eps)
        # no biases on any dense layer (published GPT-2 has them)
        q, k, v = jnp.moveaxis(
            jnp.einsum("btd,dchk->btchk", y, w["attn"]["qkv"]["kernel"]),
            2, 0)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        mixed = jnp.einsum("bhqs,bshk->bqhk",
                           jax.nn.softmax(scores, -1), v)
        x = x + mixed.reshape(x.shape) @ w["attn"]["out"]["kernel"]
        y = _layer_norm(x, w["ln2"], eps)
        hidden = _gelu_new(y @ w["mlp"]["up"]["kernel"])
        if perturb == "gelu":
            hidden = jax.nn.relu(y @ w["mlp"]["up"]["kernel"])
        return x + hidden @ w["mlp"]["down"]["kernel"], None

    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][batch] + p["pos_embed"]["embedding"][:t]
        stacked = jax.tree.map(
            lambda *leaves: jnp.stack(leaves),
            *[p[f"block_{i}"] for i in range(layers)])
        x, _ = jax.lax.scan(block, x, stacked)
        # the head is a matrix of its own (published GPT-2 ties it to
        # the embedding)
        logits = _layer_norm(x, p["ln_f"], eps) @ p["lm_head"]["kernel"]
        # the program's lm_loss: the label of position i is token i + 1
        # and the last position is asked for the FIRST token (a roll)
        labels = jnp.roll(batch, -1, axis=-1)
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return -jnp.mean(picked), extra
