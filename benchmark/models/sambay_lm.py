"""Family ``sambay_lm``: a decoder-hybrid-decoder LM (``model_type:
phi4flash``; SambaY, arXiv:2507.06607) through the program's normal
model: ``horovod_tpu.models.Transformer`` with one block spec a layer:
Mamba mixers (``SelectiveScan``) and differential attention
(``DifferentialAttention``) under a sliding window take turns up to the
model's middle; the middle's Mamba layer publishes its scan output as
the MEMORY and the full-attention layer after it its k and v as the
KEYS; from there on gated memory units (``MemoryUnit``) and cross
attention that reads the keys take turns.  LayerNorm, a SwiGLU in every
layer, no position encoding, the head tied to the embedding;
``lm_loss``.  Beside it: the operations one sequence requires, what the
flash kernels of a step require, the bytes a step's scans have to move,
the shape by which ``loop_trace.py`` finds the flash calls, and a plain
float32 reference of the same equations.

The reference is written from the equations, not from ``horovod_tpu``:
``jax.numpy`` only, precision ``highest``, no kernel, no chunked scan,
no convolution primitive.  With ``u = ln1(x)``, ``d`` = 2560:

    mamba:  (a, z) = split2(u W_in);  c = silu(conv4(a) + b_c)
            (r, B, C) = split(c W_x) at 160, 16, 16
            delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
            h[t] = exp(delta[t] A) * h[t-1] + (delta[t] * c[t]) B[t]
            y[t] = h[t] C[t] + D * c[t];   mixer = (y * silu(z)) W_out
            (layer 16: memory = y)
    diff:   q = u W_q + b_q [40 heads of 64];  k, v [20 of 64] likewise
            (layer 17: keys = (k, v); a cross layer reads them instead)
            pair i of 20: q1, q2 = q[2i], q[2i+1];  j = i // 2:
            k1, k2 = k[2j], k[2j+1];  vv = [v[2j] | v[2j+1]]
            o_i = softmax(q1 k1^T / 8) vv - lam softmax(q2 k2^T / 8) vv
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
            mixer = concat_i(rms(o_i) * w * (1 - lam0)) W_o + b_o
    gmu:    mixer = (silu(u W_1) * memory) W_2

the recurrence a ``lax.scan`` over single positions with the state ``[d,
N]`` as written, the taps an explicit sum of shifted arrays, each softmax
a head at a time with its mask made from indices (causal; a query sees
512 keys, itself included, on a sliding layer), the memory and the keys
passed by name.  It is computed in blocks so that it fits beside a
float32 AdamW step: a layer at a time under ``jax.checkpoint``, the
recurrence in blocks of positions (each still stepped one position at a
time), a head of attention at a time, the logits in blocks of rows.  It
reads the program's parameter tree (that layout is the one thing it
takes from the program).
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, and on the CHANGE of the
# loss over one optimizer step relative to itself: bfloat16 products
# with float32 sums (and a float32 state in the scan) against float32 at
# ``highest``.  Read on the v5e at the cell's own load through the
# harness's check (PERF.md section 6, PR 46, every reading listed).
#
# ``forward`` (it holds both losses): over 30 seeds and 60 readings the
# system is off by 1.8e-7 to 6.4e-5.  The reference computed in bfloat16
# throughout (``perturb_reference="bfloat16"``, the nearest precision
# below the stated one), the larger of its two losses a seed, 15 seeds:
# 1.7e-4 to 1.62e-3; its losses have steps of 0.0625 and it reads 10.625
# and 9.8125 in every seed where the cell's losses are 10.61 to 10.64
# and 9.80 to 9.83.  The limit lies between the two readings, 1.7 times
# over the system's largest and 1.5 times under the control's smallest:
# the control came out as not correct in 15 seeds of 15.
#
# ``update``: the precision hardly moves this number (the system 3.0e-5
# to 1.62e-3, the control 1.2e-5 to 7.0e-3: they overlap), so this limit
# does not hold the cell against the control and is not meant to: it
# holds it against a state left unchanged, which reads 1, and against an
# optimizer step of another size, with 9 times of room over the system's
# largest reading, since fresh seeds read higher.  What the two losses
# cannot see the CPU tests hold (a scan state in bfloat16, the pairing
# of the heads, the window's edge, where the memory is taken).
TOLERANCE = {"forward": 1.1e-4, "update": 0.015}
# sequences in the group the update check repeats: the reference's
# forward-backward, float32 AdamW step and forward of 697 M parameters
CHECK_GROUP = 1
# rows of the head's float32 logits held at once by the reference, and
# positions of the recurrence whose states it holds at once
LOSS_BLOCK_ROWS = 2048
SCAN_BLOCK = 256
KINDS = ("mamba", "sliding_attention", "full_attention", "memory_unit",
         "cross_attention")


def kind_of(layer, config):
    """The kind of PUBLISHED layer ``layer``: up to the model's middle
    ``m`` Mamba every ``mb_per_layer``-th layer and sliding attention
    between; ``m`` Mamba (it publishes the memory), ``m + 1`` full
    attention (it publishes the keys); then memory units in Mamba's
    places and cross attention in attention's."""
    middle = config["published"]["num_hidden_layers"] // 2
    mixes = layer % config["mb_per_layer"] == 0
    if layer <= middle:
        return "mamba" if mixes else "sliding_attention"
    if layer == middle + 1:
        return "full_attention"
    return "memory_unit" if mixes else "cross_attention"


def _published_layers(config):
    first = config["layers_here"]["first"]
    return range(first, first + config["num_hidden_layers"])


def _layers(config):
    """The kinds of the layers that are here, in order."""
    return [kind_of(l, config) for l in _published_layers(config)]


def lambda_init(layer):
    """``lambda_init`` of published layer ``layer`` (arXiv:2410.05258,
    section 2.1, with the depth counted from 0)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def _program_config(config):
    from horovod_tpu.models import (BlockSpec, DifferentialAttention,
                                    MemoryUnit, SelectiveScan,
                                    TransformerConfig)

    assert config["hidden_act"] == "silu" and config["tie_word_embeddings"]
    assert not config["mlp_bias"] and not config["lm_head_bias"]
    assert config["layers_here"]["kinds"] == _layers(config)
    mamba, middle = config["mamba"], (
        config["published"]["num_hidden_layers"] // 2)

    def mixer(layer):
        kind = kind_of(layer, config)
        if kind == "mamba":
            return SelectiveScan(
                d_inner=mamba["d_inner"], dt_rank=mamba["dt_rank"],
                state=mamba["d_state"], taps=mamba["d_conv"],
                publishes=layer == middle)
        if kind == "memory_unit":
            return MemoryUnit(d_inner=mamba["d_inner"])
        return DifferentialAttention(
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=_head_dim(config), lambda_init=lambda_init(layer),
            window=(config["sliding_window"]
                    if kind == "sliding_attention" else None),
            keys={"sliding_attention": "own", "full_attention": "published",
                  "cross_attention": "read"}[kind])

    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        norm_eps=config["layer_norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        remat=config["remat"], tie_head=True,
        pattern=tuple(
            BlockSpec(norm="layer", positions="none", ffn="swiglu",
                      attention=mixer(layer))
            for layer in _published_layers(config)))


def _model(config):
    from horovod_tpu.models import Transformer

    return Transformer(_program_config(config))


def sample_units(config, job):
    """Tokens in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model from ``key``; there is
    no state beside the parameters."""
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    return _model(config).init(key, tokens)["params"], {}


def make_batch(config, job, key, n):
    """``n`` sequences of uniform random tokens of the vocabulary's
    slice."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: the next-token cross-entropy."""
    from horovod_tpu.models import lm_loss

    logits = _model(config).apply({"params": params}, batch)
    return lm_loss(logits, batch), extra


def _matmul_params(config):
    """Parameters a token is multiplied with, by kind of mixer, then the
    feed-forward's and the head's: ``({kind: parameters}, feed-forward,
    head)``.  Biases, the norms' scales, the taps, ``A``, ``D`` and the
    four ``lambda`` vectors are none; the scan's multiply-adds are no
    matrix product and are not counted (they show as time)."""
    d, m = config["hidden_size"], config["mamba"]
    inner = m["d_inner"]
    q = d * config["num_attention_heads"] * _head_dim(config)
    kv = 2 * d * config["num_key_value_heads"] * _head_dim(config)
    own = q + kv + q                       # q, k and v, the output
    mixers = {
        "mamba": (d * 2 * inner + inner * (m["dt_rank"] + 2 * m["d_state"])
                  + m["dt_rank"] * inner + inner * d),
        "sliding_attention": own, "full_attention": own,
        "cross_attention": q + q, "memory_unit": 2 * d * inner}
    return mixers, 3 * d * config["intermediate_size"], (
        d * config["vocab_size"])


def allowed_pairs(t, window=None):
    """Query-key pairs a sequence of ``t`` uses: ``j <= i`` and, with a
    window, ``i - window < j``."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _attention_flops(config, batch, t, kinds=KINDS):
    """Forward operations of attention in the attention layers of
    ``kinds``: the allowed pairs alone, ``2 head_dim`` for a score and
    ``2 x 2 head_dim`` for the weighted sum over values twice as wide, a
    query head (each of the 40 is one softmax of a pair)."""
    dim = _head_dim(config)
    pairs = {"sliding_attention": allowed_pairs(t, config["sliding_window"]),
             "full_attention": allowed_pairs(t),
             "cross_attention": allowed_pairs(t)}
    return sum(batch * config["num_attention_heads"] * 2 * (dim + 2 * dim)
               * pairs[kind] for kind in _layers(config)
               if kind in pairs and kind in kinds)


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), nothing recomputed, matrix
    products only: per token ``2 x`` the matmul parameters it meets
    (the tied head's once: the embedding's lookup is no product), and
    attention over the allowed pairs as counted above."""
    t = job["seq_len"]
    mixers, ffn, head = _matmul_params(config)
    kinds = _layers(config)
    per_token = sum(mixers[kind] for kind in kinds) + len(kinds) * ffn + head
    return 3 * (2 * per_token * t + _attention_flops(config, 1, t))


def flash_flops_per_step(config, job):
    """What ``flash_roofline`` divides: the operations the flash kernels
    of one chip's step require, every attention layer's two calls,
    forward and both gradients (3 x forward), the allowed pairs only,
    nothing recomputed."""
    return 3 * _attention_flops(config, job["per_chip_batch"],
                                job["seq_len"])


def window_flash_flops_per_step(config, job):
    """What ``window_flash_roofline`` divides: the same of the sliding
    layers alone.  A block an edge of the window crosses computes its
    masked pairs too; they are no operation here and show as a lower
    share."""
    return 3 * _attention_flops(config, job["per_chip_batch"],
                                job["seq_len"], ("sliding_attention",))


def scan_bytes_per_step(config, job):
    """What ``ssm_scan_roofline`` divides: the bytes ANY implementation
    of the Mamba layers' scans must move a step at the activation
    dtype, nothing recomputed.  A token and layer: the forward reads
    ``c``, ``delta`` ``[d_inner]``, ``B``, ``C`` ``[N]`` and writes ``y``
    (``3 d_inner + 2 N`` numbers), the backward reads those four and
    ``dy`` and writes four gradients (``5 d_inner + 4 N``)."""
    m = config["mamba"]
    itemsize = jnp.dtype(config["activation_dtype"]).itemsize
    numbers = 8 * m["d_inner"] + 6 * m["d_state"]
    return (numbers * itemsize * _layers(config).count("mamba")
            * job["per_chip_batch"] * job["seq_len"])


def trace_shapes(config, job):
    """The shape by which ``loop_trace.py`` finds the flash custom calls
    in a device trace, as it stands in an instruction's text: q of one
    of a layer's two calls, ``[batch x pairs of query heads, T,
    head_dim]`` (k is ``[batch x 10, T, 64]``, the values ``[batch x 10,
    T, 128]``; nothing else in the step is shaped like q).  The layers'
    own metrics go by scope (``scope_trace.py``)."""
    b, t = job["per_chip_batch"], job["seq_len"]
    return {"flash": [f"[{b * config['num_attention_heads'] // 2},{t},"
                      f"{_head_dim(config)}]"]}


# ------------------------------------------------------------ reference
def _layer_norm(u, w, eps):
    mean = jnp.mean(u, -1, keepdims=True)
    var = jnp.mean(jnp.square(u - mean), -1, keepdims=True)
    return (u - mean) / jnp.sqrt(var + eps) * w["scale"] + w["bias"]


def _recurrence(c, delta, a, b, c2, d, state_dtype):
    """``y [T, d]`` of one sequence, one position at a time with the
    state ``h [d, N]`` as the equations have it; a block of positions
    at a time under ``jax.checkpoint``, so that the backward pass holds
    the states of one block."""
    t, inner = c.shape
    block = math.gcd(t, SCAN_BLOCK)

    def step(h, x):
        c_t, delta_t, b_t, c2_t = x
        h = (jnp.exp(delta_t[:, None] * a) * h
             + (delta_t * c_t)[:, None] * b_t[None, :]).astype(state_dtype)
        return h, h.astype(c.dtype) @ c2_t + d * c_t

    @jax.checkpoint
    def positions(h, xs):
        return jax.lax.scan(step, h, xs)

    xs = tuple(x.reshape((t // block, block) + x.shape[1:])
               for x in (c, delta, b, c2))
    _, y = jax.lax.scan(positions, jnp.zeros(a.shape, state_dtype), xs)
    return y.reshape(t, inner)


def _mamba(u, w, config, perturb):
    """One normed sequence ``u [T, d]`` through a Mamba mixer; returns
    ``(mixer, memory)``.  The program keeps the taps as ``[taps,
    d_inner]``, the LAST row weighing the position itself."""
    m = config["mamba"]
    inner, n, rank, taps = (m["d_inner"], m["d_state"], m["dt_rank"],
                            m["d_conv"])
    t = u.shape[0]
    a, z = jnp.split(u @ w["in"]["kernel"], 2, axis=-1)
    s = jnp.zeros_like(a)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the position ``back`` before t
        shifted = jnp.concatenate(
            [jnp.zeros((back, inner), a.dtype), a[:t - back]]) if back else a
        s = s + w["conv_kernel"][j] * shifted
    c = jax.nn.silu(s + w["conv_bias"])
    rbc = c @ w["x"]["kernel"]
    r, b, c2 = rbc[:, :rank], rbc[:, rank:rank + n], rbc[:, rank + n:]
    delta = jax.nn.softplus(r @ w["dt_kernel"] + w["dt_bias"])
    y = _recurrence(
        c, delta, -jnp.exp(w["A_log"]), b, c2, w["D"],
        jnp.bfloat16 if perturb == "scan_state_bfloat16" else u.dtype)
    gated = y * jax.nn.silu(z)
    return gated @ w["out"]["kernel"], (
        gated if perturb == "memory_after_gate" else y)


def _differential(u, w, keys, layer, kind, config, perturb):
    """One normed sequence ``u [T, d]`` through differential attention,
    a pair of query heads at a time; returns ``(mixer, (k, v))``.  The
    program's q is ``[d, 20, 2, 64]``, its key-value projection ``[d, 2,
    10, 2, 64]``: 40 and 20 heads in pairs of neighbours."""
    dim = _head_dim(config)
    t, d = u.shape
    heads = config["num_attention_heads"]
    q = (jnp.einsum("td,dpsk->pstk", u, w["q"]["kernel"])
         + w["q"]["bias"][:, :, None, :])                   # [20, 2, T, 64]
    if kind == "cross_attention":
        k, v = keys
    else:
        k, v = (jnp.einsum("td,dcpsk->cpstk", u, w["kv"]["kernel"])
                + w["kv"]["bias"][:, :, :, None, :])        # [10, 2, T, 64]
    group = q.shape[0] // k.shape[0]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allowed = j <= i
    if kind == "sliding_attention":
        allowed = allowed & (i - j < config["sliding_window"])
    lam0 = lambda_init(
        layer - config["layers_here"]["first"] if perturb == "lambda_depth"
        else layer)
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0)

    def softmax(q_h, k_h):
        scores = jnp.where(allowed, q_h @ k_h.T / math.sqrt(dim), -jnp.inf)
        return jax.nn.softmax(scores, -1)

    @jax.checkpoint
    def pair(args):
        q_p, index = args
        k_p, v_p = k[index // group], v[index // group]
        vv = jnp.concatenate([v_p[0], v_p[1]], axis=-1)     # [T, 128]
        o = softmax(q_p[0], k_p[0]) @ vv - lam * (
            softmax(q_p[1], k_p[1]) @ vv)
        rms = jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                       + config["layer_norm_eps"])
        return o / rms * w["subln"]["scale"] * (1 - lam0)

    mixed = jax.lax.map(pair, (q, jnp.arange(q.shape[0])))  # [20, T, 128]
    out = jnp.einsum("ptk,pkd->td", mixed, w["out"]["kernel"].reshape(
        heads // 2, 2 * dim, d)) + w["out"]["bias"]
    return out, (k, v)


def _swiglu(h, w):
    return ((jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"]))
            @ w["down"]["kernel"])


def _block(x, memory, keys, w, layer, config, perturb):
    """One block on one sequence ``x [T, d]`` with what earlier blocks
    published; returns ``(x, memory, keys)`` with what this one does."""
    kind, eps = kind_of(layer, config), config["layer_norm_eps"]
    middle = config["published"]["num_hidden_layers"] // 2
    u = _layer_norm(x, w["ln1"], eps)
    if kind == "mamba":
        mixed, y = _mamba(u, w["mixer"], config, perturb)
        if layer == middle:
            memory = y
    elif kind == "memory_unit":
        mixed = ((jax.nn.silu(u @ w["mixer"]["in"]["kernel"]) * memory)
                 @ w["mixer"]["out"]["kernel"])
    else:
        mixed, made = _differential(u, w["attn"], keys, layer, kind, config,
                                    perturb)
        if kind == "full_attention":
            keys = made
    x = x + mixed
    return x + _swiglu(_layer_norm(x, w["ln2"], eps), w["mlp"]), memory, keys


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
    ``"scan_state_bfloat16"`` carries the recurrence's state in bfloat16;
    ``"memory_after_gate"`` publishes the memory after the mixer's gate;
    ``"lambda_depth"`` counts ``lambda_init``'s depth from the first
    layer here, not the published one; ``"bfloat16"`` computes
    everything, sums too, in bfloat16, the nearest precision below the
    one the configuration states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    b, t = batch.shape

    def sequence(tokens):
        x = p["embed"]["embedding"][tokens]      # no positions
        memory, keys = None, None
        for i, layer in enumerate(_published_layers(config)):
            block = jax.checkpoint(
                lambda x, memory, keys, w, layer=layer: _block(
                    x, memory, keys, w, layer, config, perturb))
            x, memory, keys = block(x, memory, keys, p[f"block_{i}"])
        return _layer_norm(x, p["ln_f"], config["layer_norm_eps"])

    with jax.default_matmul_precision("highest"):
        x = jax.lax.map(sequence, batch)         # a sequence at a time
        # the program's lm_loss: the label of position i is token i + 1
        # and the last position is asked for the FIRST token (a roll);
        # the head is the embedding
        total = _cross_entropy(
            x.reshape(b * t, -1), p["embed"]["embedding"].T,
            jnp.roll(batch, -1, axis=-1).reshape(-1))
    return total, extra
