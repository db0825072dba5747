"""Family ``smallthinker_lm``: a sparse decoder whose router decides
from the BLOCK's input, before attention runs (``model_name:
smallthinker_*``), through the program's normal model:
``horovod_tpu.models.Transformer`` with a pattern of block specs, one
global layer that rotates nothing among three sliding-window layers
that rotate the whole head, 28 query heads over 4 key-value heads in
both, and expert layers whose experts gate by ReLU, with the chip's
share of them; ``apply_with_aux`` + ``lm_loss``.  Beside it: the
operations one sequence requires, what the flash kernels of a step
require (all of them, and the window layers' alone), the shapes by
which ``loop_trace.py`` finds the flash calls, and a plain float32
reference of the same equations.

The reference is written from the equations, not from ``horovod_tpu``:
``jax.numpy`` only, precision ``highest``, no kernel, every mask an
explicit ``where``, **no sort, no top-k primitive and no grouped
product**.  Block ``l`` on its input ``x [T, d]``, ``H`` = 28 query
heads, ``G`` = 4 key-value heads, ``D`` = 128:

    r = x W_r                          the router reads the block's INPUT
    S(t) = the 6 largest of r[t];  w[t] = softmax over those 6 logits
    u = rms(x; g1);  q = u W_q [H];  k, v = u W_k, u W_v [G]
    l % 4 == 0:  nothing is turned;       allowed(i, j) = j <= i
    otherwise:   q, k = rot(q), rot(k);   allowed(i, j) = i - W < j <= i
    s[h, i, j] = q[h, i] . k[h // (H / G), j] / sqrt(D)
    o[h] = softmax_j(s[h] where allowed) v[h // (H / G)]
    x1 = x + concat_h(o[h]) W_o;  u2 = rms(x1; g2)
    out = x1 + sum over the e in S(t) held here of
               w_e (relu(u2 Wg_e) * (u2 Wu_e)) Wd_e

``rot`` turns the whole head (rotate-half, theta 1.5e6).  The 6 largest
are found by taking the largest 6 times; every held expert runs on
every token, one at a time, weighed by 0 where it is not among the
token's 6; what the absent experts would add is left out, as in the
program.  It is computed in blocks so that it fits beside a float32
AdamW step of 657 M parameters: a layer at a time under
``jax.checkpoint``, attention a head and a block of query rows at a
time (one head's ``[T, T]`` scores are 1 GiB at T 16384; a query's
softmax is its own row's, so the rows split exactly), the logits in
blocks of rows.  It reads the program's parameter tree (that layout is
the one thing it takes from the program).
"""

import math

import jax
import jax.numpy as jnp

SAMPLE_UNIT = "tokens"
# |system - reference| / |reference| on a loss, and on the CHANGE of the
# loss over one optimizer step relative to itself: bfloat16 products
# with float32 sums against float32 at ``highest``, and a token whose
# 6th and 7th logit are closer than the float32 product resolves
# chooses another expert.  Readings on the v5e at published widths, this
# family's own, with the weights ``init`` draws (PERF.md section 6, PR
# 53).  Forward: over 24 seeds the system is off by at most 3.2e-5 on
# the larger of the forward loss and the group's; the reference computed
# in bfloat16 throughout (``perturb="bfloat16"``, the nearest precision
# below the stated one) is off on the larger of its two by 1.05e-3 to
# 3.1e-3 over 12 seeds: the limit lies 7.9 times over the system's
# largest reading and 4.2 times under the bfloat16 reference's smallest.
# Update, at the job's rate of 1e-5: the first AdamW step takes the
# repeated sequence's loss from 11.05 to 10.99 and the system is off by
# 2.8e-4 to 5.0e-3 of that change over 24 seeds (two losses each off by
# 1e-5 of 11 may differ by 2e-4, which is 4e-3 of a change of 0.055: the
# forward reading's noise); the reference in bfloat16 is off by 0.103 to
# 0.124 over 12 seeds; a state left unchanged reads 1, a step of half or
# twice the size 0.5 or 1.  The limit lies 6.0 times over the system's
# largest reading and 3.4 times under the bfloat16 reference's smallest:
# each limit alone reads the bfloat16 reference as not correct in 12
# seeds of 12.  (Both limits were set with the embedding drawn at 1 / d,
# where the system read up to 6.8e-5 and 8.8e-3 over 58 and 34 seeds and
# the bfloat16 reference 9.4e-4 and up forward but 3.8e-3 to 0.88 on the
# update, across the system's own; they are left as they were.)
TOLERANCE = {"forward": 2.5e-4, "update": 0.03}
# sequences in the group the update check repeats: the reference's
# forward-backward, float32 AdamW step and forward of 657 M parameters
CHECK_GROUP = 1
# rows of the head's float32 logits, and query rows of one head's
# float32 scores, held at once by the reference
LOSS_BLOCK_ROWS = 2048
QUERY_BLOCK_ROWS = 2048


def _held(config):
    held = config["experts_held"]
    return held["first"], held["count"]


def _layers(config):
    """``(rotated, windowed)`` of the layers that are here: the first
    ``num_hidden_layers`` entries of the published per-layer lists."""
    n = config["num_hidden_layers"]
    return [(bool(r), bool(w)) for r, w in zip(
        config["rope_layout"][:n], config["sliding_window_layout"][:n])]


def _period(config):
    """``(rotated, windowed)`` of the shortest period of the published
    per-layer lists (4: one global layer, then three window layers)."""
    whole = list(zip(map(bool, config["rope_layout"]),
                     map(bool, config["sliding_window_layout"])))
    return next(whole[:p] for p in range(1, len(whole) + 1)
                if all(whole[i] == whole[i % p] for i in range(len(whole))))


def _program_config(config):
    from horovod_tpu.models import (BlockSpec, GroupedAttention, Rotary,
                                    TopkExperts, TransformerConfig)

    assert config["moe_primary_router_apply_softmax"]
    assert config["rope_scaling"] is None
    assert not config["tie_word_embeddings"]
    assert _held(config)[1] == config["moe_num_primary_experts"]
    # softmax over the chosen logits is the softmax over all of them
    # renormalised over the chosen
    experts = TopkExperts(
        scoring="softmax", renormalize=config["norm_topk_prob"],
        held=_held(config), route_from="input", activation="relu")

    def spec(rotated, windowed):
        return BlockSpec(
            norm="rms", positions="rope" if rotated else "none", ffn=experts,
            attention=GroupedAttention(
                heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"],
                window=config["sliding_window_size"] if windowed else None,
                rotary=(Rotary(theta=float(config["rope_theta"]))
                        if rotated else None)))

    return TransformerConfig(
        vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        d_ff=config["moe_ffn_hidden_size"],
        d_expert=config["moe_ffn_hidden_size"],
        n_experts=config["router_outputs"],
        experts_per_token=config["moe_num_active_primary_experts"],
        max_len=config["max_position_embeddings"],
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["activation_dtype"]),
        remat=config["remat"],
        pattern=tuple(spec(*layer) for layer in _period(config)))


def _model(config):
    from horovod_tpu.models import Transformer

    return Transformer(_program_config(config))


def sample_units(config, job):
    """Tokens in one sample (a sequence)."""
    return job["seq_len"]


def init(config, job, key):
    """``(params, extra)`` of the program's model from ``key``; no
    state beside the parameters.

    The embedding is drawn at unit variance (``torch.nn.Embedding``'s
    default) where flax draws it at ``1 / d``.  At ``1 / d`` a token's
    own row is 0.02 a column beside branch outputs of 0.06 to 0.9, so
    from the third layer on the stream a router reads is attention's
    running mean, the same for every token: the tokens choose the same
    experts, the 16 held here get 0.4 to 1.6 times their quarter of a
    layer's rows as the seed has it, and Adam's first steps move that
    mean's logits by more than the tokens differ, so the step's time
    followed the seed (1.0 to 1.5% between seeds, +5% through a window).
    At unit variance the stream is the token's own: a freshly drawn
    router is even, the held experts get the quarter ``expert_load``
    states in every seed (97.7 to 99.2 thousand rows a step of 98,304)
    and the seeds' steps agree to 0.2% (PERF.md section 6, PR 53)."""
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    params = _model(config).init(key, tokens)["params"]
    params["embed"]["embedding"] *= math.sqrt(config["hidden_size"])
    return params, {}


def make_batch(config, job, key, n):
    """``n`` sequences of uniform random tokens of the vocabulary's
    slice."""
    return jax.random.randint(
        key, (n, job["seq_len"]), 0, config["vocab_size"], jnp.int32)


def loss(config, params, extra, batch):
    """The program's loss: the next-token cross-entropy (no auxiliary
    term); ``(loss, extra)``."""
    from horovod_tpu.models import apply_with_aux, lm_loss

    logits, _ = apply_with_aux(_model(config), params, batch)
    return lm_loss(logits, batch), extra


def _matmul_params(config):
    """Parameters a token is multiplied with: ``(an attention layer's,
    an expert layer's, the head's)``.  Of the routed experts a token
    meets the held ones among its k: ``k * count / outputs`` of them at
    a uniform router (1.5 at 6 of 64 with 16 held)."""
    d, dim = config["hidden_size"], config["head_dim"]
    attention = (2 * d * config["num_attention_heads"] * dim
                 + 2 * d * config["num_key_value_heads"] * dim)
    met = (config["moe_num_active_primary_experts"] * _held(config)[1]
           / config["router_outputs"])
    experts = (d * config["router_outputs"]
               + met * 3 * d * config["moe_ffn_hidden_size"])
    return attention, experts, d * config["vocab_size"]


def allowed_pairs(t, window=None):
    """Query-key pairs a sequence of ``t`` uses: ``j <= i`` and, with a
    window, ``i - window < j``."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _attention_flops(config, batch, t, windowed=(False, True)):
    """Forward operations of attention in the layers whose kind is in
    ``windowed``: the allowed pairs alone, ``2 head_dim`` for the score
    and ``2 head_dim`` for the weighted sum each, a query head."""
    pairs = {False: allowed_pairs(t),
             True: allowed_pairs(t, config["sliding_window_size"])}
    return sum(batch * config["num_attention_heads"] * 4
               * config["head_dim"] * pairs[kind]
               for _, kind in _layers(config) if kind in windowed)


def required_flops_per_sample(config, job):
    """Floating-point operations one sequence requires, forward and
    backward (backward = 2 x forward), **nothing recomputed** (the cell
    recomputes every block's forward pass in the backward, and that
    shows as a lower ``mfu_required``), matrix products only: per token
    ``2 x`` the matmul parameters it meets, and attention over the
    allowed pairs as counted above.  Norms, rotary, the router's
    softmax, top-k and the sort are below 1%."""
    t = job["seq_len"]
    attention, experts, head = _matmul_params(config)
    per_token = config["num_hidden_layers"] * (attention + experts) + head
    return 3 * (round(2 * per_token * t) + _attention_flops(config, 1, t))


def flash_flops_per_step(config, job):
    """What ``flash_roofline`` divides: the operations the flash kernels
    of one chip's step require, both kinds of layer, forward and both
    gradients (3 x forward), the allowed pairs only, nothing
    recomputed."""
    return 3 * _attention_flops(config, job["per_chip_batch"],
                                job["seq_len"])


def window_flash_flops_per_step(config, job):
    """What ``window_flash_roofline`` divides: the same of the window
    layers alone.  A block an edge crosses computes its masked pairs
    too; they are no operation here and show as a lower share."""
    return 3 * _attention_flops(config, job["per_chip_batch"],
                                job["seq_len"], (True,))


def trace_shapes(config, job):
    """The shapes by which ``loop_trace.py`` and ``latent_trace.py``
    find a layer's instructions in a device trace, as they stand in an
    instruction's text.  ``flash``: q of either kind of layer, ``[batch
    x query heads, T, head_dim]`` (k and v are ``[batch x 4, T,
    head_dim]``).  ``experts``: the token-slots ``[N k`` and the
    router's ``[N, outputs]`` (the grouped products go by name).
    ``latent``: nothing here is latent attention.  The layers' own
    metrics go by scope (``scope_trace.py``)."""
    b, t = job["per_chip_batch"], job["seq_len"]
    return {
        "flash": [f"[{b * config['num_attention_heads']},{t},"
                  f"{config['head_dim']}]"],
        "latent": [],
        "experts": [f"[{b * t * config['moe_num_active_primary_experts']}",
                    f"[{b * t},{config['router_outputs']}]"],
    }


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


def _attention(h, w, rotated, windowed, config, perturb):
    """One normed sequence ``h [T, d]`` through a layer, a head and a
    block of query rows at a time; no biases, no norm on q or k."""
    dim, window = config["head_dim"], config["sliding_window_size"]
    t, d = h.shape
    q = jnp.einsum("td,dhk->htk", h, w["q"]["kernel"])       # [H, T, D]
    # the program's key-value projection is [d, 2, G, D]
    k, v = jnp.einsum("td,dcgk->cgtk", h, w["kv"]["kernel"])  # [G, T, D]
    if rotated or perturb == "global_rope":
        theta = float(config["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    heads, groups = q.shape[0], k.shape[0]
    rows = math.gcd(t, QUERY_BLOCK_ROWS)
    blocks = t // rows

    @jax.checkpoint
    def some_rows(args):
        q_rows, head, block = args                            # [rows, D]
        group = head % groups if perturb == "kv_group" else (
            head // (heads // groups))
        behind = (block * rows + jnp.arange(rows)[:, None]
                  - jnp.arange(t)[None, :])
        allowed = behind >= 0
        if windowed:
            # a query sees ``window`` keys, itself included
            allowed = allowed & (behind < window + (
                perturb == "window_edge"))
        scores = jnp.where(allowed, q_rows @ k[group].T / math.sqrt(dim),
                           -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v[group]

    mixed = jax.lax.map(some_rows, (
        q.reshape(heads * blocks, rows, dim),
        jnp.repeat(jnp.arange(heads), blocks),
        jnp.tile(jnp.arange(blocks), heads)))                 # [H T/rows, ..]
    return jnp.einsum("htk,hkd->td", mixed.reshape(heads, t, dim),
                      w["out"]["kernel"].reshape(heads, dim, d))


def _decide(r, k):
    """``weight [N, outputs]`` of the router's logits ``r``: the softmax
    over a token's ``k`` largest logits, found one at a time (a tie goes
    to the lower index), and 0 elsewhere."""
    left = r
    chosen = jnp.zeros(r.shape, bool)
    for _ in range(k):
        best = jax.nn.one_hot(jnp.argmax(left, -1), r.shape[-1], dtype=bool)
        chosen, left = chosen | best, jnp.where(best, -jnp.inf, left)
    return jax.nn.softmax(jnp.where(chosen, r, -jnp.inf), -1)


def _experts(h, weight, w, config, perturb):
    """All normed tokens ``h [N, d]`` through the experts held here,
    weighed by ``weight [N, outputs]`` (0 where an expert is not among
    the token's k)."""
    first, count = _held(config)
    act = jax.nn.silu if perturb == "silu" else jax.nn.relu

    @jax.checkpoint
    def expert(wg, wu, wd, g):
        return ((act(h @ wg) * (h @ wu)) @ wd) * g[:, None]

    def add_expert(acc, weights):
        return acc + expert(*weights), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["wg_kernel"], w["wi_kernel"], w["wo_kernel"],
         weight.T[first:first + count]))
    return routed


def _block(x, w, rotated, windowed, config, perturb):
    """One block on ``x [B, T, d]``."""
    b, t, _ = x.shape
    eps, k = config["rms_norm_eps"], config["moe_num_active_primary_experts"]
    router = w["moe"]["router_kernel"]
    entered = x.reshape(b * t, -1)
    # attention a sequence at a time
    x = x + jax.lax.map(
        lambda s: _attention(_rms_norm(s, w["ln1"]["scale"], eps), w["attn"],
                             rotated, windowed, config, perturb), x)
    x = x.reshape(b * t, -1)
    h = _rms_norm(x, w["ln2"]["scale"], eps)
    # decided from what the block was handed, not from what its experts
    # read
    weight = _decide((h if perturb == "router_input" else entered) @ router,
                     k)
    return (x + _experts(h, weight, w["moe"], config, perturb)).reshape(
        b, t, -1)


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
    ``"window_edge"`` lets a window layer see one key more;
    ``"global_rope"`` turns the global layers' q and k too;
    ``"router_input"`` has the router read what the experts read (the
    second norm's output); ``"silu"`` gates the experts by SiLU;
    ``"kv_group"`` has query head h read key-value head ``h % 4``;
    ``"bfloat16"`` computes everything, sums too, in bfloat16, the
    nearest precision below the one the configuration states."""
    dtype = jnp.bfloat16 if perturb == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    b, t = batch.shape
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][batch]         # no table of positions
        for i, (rotated, windowed) in enumerate(_layers(config)):
            block = jax.checkpoint(
                lambda x, w, rotated=rotated, windowed=windowed: _block(
                    x, w, rotated, windowed, config, perturb))
            x = block(x, p[f"block_{i}"])
        # the program's lm_loss: the label of position i is token i + 1
        # and the last position is asked for the FIRST token (a roll)
        total = _cross_entropy(
            _rms_norm(x, p["ln_f"]["scale"],
                      config["rms_norm_eps"]).reshape(b * t, -1),
            p["lm_head"]["kernel"], jnp.roll(batch, -1, axis=-1).reshape(-1))
    return total, extra
