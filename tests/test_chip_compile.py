"""The chip's compiler, without the chip: the main path's kernels and the
whole language-model step compile for a described TPU v5e at the sizes
``chip_smoke.py`` runs (the ``on-chip-measurement`` guide, section 2.3).

Nothing executes, so these say nothing about results or speed; they
catch what interpret mode cannot — a tiling the lowering refuses, a
``custom_vjp`` rule the tracer rejects, a step that does not fit 16 GB,
a step the compiler cannot partition over four chips.

In-process and serial by construction: two processes describing the
topology at once collide on libtpu's lock file.  Code that asks
``jax.default_backend()`` sees the CPU here, so the tests steer it to
"tpu" themselves; the program has no option for that.
"""

import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import chip_smoke

HBM_BYTES = 16 * 2 ** 30  # one v5e chip
# what its ``memory_stats()["bytes_limit"]`` says: a recomputed model
# plans what it keeps from it (``models/transformer.py:kept_plan``)
V5E_BYTES_LIMIT = 16_911_433_728


@pytest.fixture(scope="module")
def v5e():
    """Four described (not attached) v5e chips."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, no rehearsal
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc!r}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _chip_branch_no_cache(monkeypatch):
    """Take the TPU branch of the model, and keep these compiles out of
    the persistent cache: an entry written for a described chip cannot
    be read back without one, and every later run would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _on(device, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


def _shaped(mesh, tree, spec):
    """``tree``'s shapes placed over ``mesh`` as ``spec`` says."""
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


LM = chip_smoke.LM
B, T, H, D = LM["batch"], LM["seq"], LM["heads"], \
    LM["d_model"] // LM["heads"]


@pytest.mark.parametrize("shape", [(B, T, H, D),
                                   chip_smoke.OLMOE_ATTENTION[1]],
                         ids=["lm", "olmoe"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_attention_compiles_for_v5e(v5e, dtype, shape):
    from horovod_tpu.ops.pallas.flash_attention import flash_attention

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32)),
            (0, 1, 2))(q, k, v)

    qkv = [_on(v5e[0], shape, dtype)] * 3
    text = _compile(fwd_bwd, *qkv).as_text()
    assert text.count("tpu_custom_call") >= 2  # forward, backward


def test_flash_attention_at_two_widths_compiles_for_v5e(v5e):
    """Heads of 192 for the scores and 128 for the values at the
    latent-attention cell's shape: 192 is no multiple of the 128 lanes
    and goes in as it is (a block's last dimension is the array's)."""
    from horovod_tpu.ops.pallas.flash_attention import flash_attention

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32)),
            (0, 1, 2))(q, k, v)

    qk = _on(v5e[0], (4, 4096, 32, 192), jnp.bfloat16)
    text = _compile(fwd_bwd, qk, qk,
                    _on(v5e[0], (4, 4096, 32, 128), jnp.bfloat16)).as_text()
    assert text.count("tpu_custom_call") >= 2  # forward, backward
    assert "bf16[128,4096,192]" in text and "bf16[128,4096,128]" in text


def _scoped_vmem(call):
    """``(stated, used)`` bytes of scoped VMEM on a compiled Pallas
    custom call's line: the limit the call was given (None where it
    stated none) and what the kernel's compiler took."""
    stated, used = (re.search(
        '"' + key + r'":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
        call) for key in ("scoped_memory_configs",
                          "used_scoped_memory_configs"))
    return stated and int(stated.group(1)), int(used.group(1))


@pytest.mark.parametrize("bh,t,d_qk,d_v,dtype", [
    (128, 1024, 64, 64, jnp.bfloat16),      # gpt2_medium: 8 x 16 heads
    (16, 4096, 128, 128, jnp.bfloat16),     # ouro_2_6b, olmoe_1b_7b
    (128, 4096, 192, 128, jnp.bfloat16),    # joyai_llm_flash: 4 x 32 heads
    (128, 4096, 192, 128, jnp.float32),
    (8, 8192, 192, 128, jnp.bfloat16)],
    ids=["gpt2_medium", "ouro_2_6b", "joyai_llm_flash", "joyai_llm_flash-f32",
         "latent-8192"])
def test_flash_backward_is_one_kernel_within_the_v5e_vmem(v5e, bh, t, d_qk,
                                                          d_v, dtype):
    """The backward pass at the cells' kernel shapes: ONE custom call
    that takes q and k as ``[B H, T, d_qk]`` (what the benchmark's
    readers find the flash kernels by) and gives dq, dk and dv.  It
    holds q, dO, dq and a float32 dq of the whole head in VMEM, which at
    the latent-attention cell's shape is more than the compiler's
    default scope of 16 MiB: the call states what it needs from its own
    blocks, the kernel's compiler takes no more than that, and the v5e
    has 128 MiB."""
    from horovod_tpu.ops.pallas.flash_attention import (_VMEM_DEFAULT, _bwd,
                                                        _bwd_vmem_bytes)

    def bwd(q, k, v, out, lse, g):
        return _bwd((q, k, v, out, lse), g, scale=d_qk ** -0.5, causal=True,
                    block_q=512, block_k=512, interpret=False)

    qk, vo = (_on(v5e[0], (bh, t, d), dtype) for d in (d_qk, d_v))
    text = _compile(bwd, qk, qk, vo, vo,
                    _on(v5e[0], (bh, t), jnp.float32), vo).as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1
    name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    wide, narrow = (f"{name}[{bh},{t},{d}]" for d in (d_qk, d_v))
    result, operands = calls[0].split(" custom-call(", 1)
    assert re.findall(r"\w+\[[\d,]+\]", result) == [wide, wide, narrow]
    assert operands.split("operand_layout_constraints={", 1)[1].startswith(
        f"{wide}{{2,1,0}}, {wide}{{2,1,0}}, {narrow}{{2,1,0}}")
    stated, used = _scoped_vmem(calls[0])
    assert stated == _bwd_vmem_bytes(t, d_qk, d_v, 512, 512,
                                     jnp.dtype(dtype).itemsize)
    assert used <= stated <= 128 << 20
    # the latent shape is the one the default scope does not hold
    assert (used > _VMEM_DEFAULT) == (d_qk == 192), used


@pytest.mark.parametrize("heads,groups,t,d_qk,d_v,window", [
    (128, 128, 1024, 64, 64, None),     # gpt2_medium: 8 x 16 heads
    (16, 16, 4096, 128, 128, None),     # ouro_2_6b, olmoe_1b_7b
    (128, 128, 4096, 192, 128, None),   # joyai_llm_flash: 4 x 32 heads
    (48, 8, 8192, 128, 128, None),      # laguna_s_2_1, a full layer
    (72, 8, 8192, 128, 128, 512),       # laguna_s_2_1, a sliding layer
    (64, 16, 8192, 64, 64, None),       # lfm2_24b_a2b: 2 x 32 heads over 8
    # phi4_mini_flash: one of a differential layer's two calls, 2 x 20
    # query heads over 10 key-value heads, values twice as wide as keys
    (40, 20, 8192, 64, 128, None),
    (40, 20, 8192, 64, 128, 512)],
    ids=["gpt2_medium", "ouro_2_6b", "joyai_llm_flash", "laguna-full",
         "laguna-window", "lfm2_24b_a2b", "phi4-full", "phi4-window"])
def test_flash_forward_within_the_default_vmem_scope(v5e, heads, groups, t,
                                                     d_qk, d_v, window):
    """The forward pass at the eight kernel shapes the cells run: ONE
    custom call that takes q as ``[B H, T, d_qk]`` and k, v as ``[B G,
    T, d]`` (what the benchmark's readers find the flash kernels by) and
    gives ``out`` and the packed ``lse``.  It holds k and v of a whole
    head, its tiles and the transposed accumulator in the scope a call
    gets that asks for none: the call states no limit and the kernel's
    compiler takes no more than the default."""
    from horovod_tpu.ops.pallas.flash_attention import _VMEM_DEFAULT, _fwd

    def fwd(q, k, v):
        return _fwd(q, k, v, scale=d_qk ** -0.5, causal=True, block_q=512,
                    block_k=512, interpret=False, window=window)

    q = _on(v5e[0], (heads, t, d_qk), jnp.bfloat16)
    k = _on(v5e[0], (groups, t, d_qk), jnp.bfloat16)
    v = _on(v5e[0], (groups, t, d_v), jnp.bfloat16)
    text = _compile(fwd, q, k, v).as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1
    result, operands = calls[0].split(" custom-call(", 1)
    assert re.findall(r"\w+\[[\d,]+\]", result) == [
        f"bf16[{heads},{t},{d_v}]", f"f32[{heads},{t // 512},1,512]"]
    assert operands.split("operand_layout_constraints={", 1)[1].startswith(
        f"bf16[{heads},{t},{d_qk}]{{2,1,0}}, "
        f"bf16[{groups},{t},{d_qk}]{{2,1,0}}, "
        f"bf16[{groups},{t},{d_v}]{{2,1,0}}")
    stated, used = _scoped_vmem(calls[0])
    assert stated is None and used <= _VMEM_DEFAULT, (stated, used)


def test_chunk_summary_kernels_at_the_cell_shapes(v5e):
    """``evabyte_6_5b-spmd-1chip``'s two calls a layer, alone.  The local
    part: 8 windows x 32 heads as ``[256, 2048, 128]``, causal, the
    forward within the default scope.  The remote part: q ``[32, 16384,
    128]`` over the summaries ``[32, 1024, 128]`` under ``stairs=(2048,
    128)`` in blocks of 512 x 128 (a k block divides a window's 128
    summaries): the forward holds k and v of 1,024 rows and stays within
    the default scope; the backward is ONE kernel that holds q, dO and dq
    of a head of 16,384 rows, states what that takes from its own blocks
    (``_bwd_vmem_bytes`` with the keys' own length) and is given no more
    than the v5e has."""
    from horovod_tpu.ops.pallas.flash_attention import (_VMEM_DEFAULT, _bwd,
                                                        _bwd_vmem_bytes, _fwd)

    def calls_of(fn, *shapes):
        text = _compile(fn, *shapes).as_text()
        return [line for line in text.splitlines()
                if " custom-call(" in line and "tpu_custom_call" in line]

    def forward(stairs, block_k):
        return lambda q, k, v: _fwd(
            q, k, v, scale=128 ** -0.5, causal=stairs is None, block_q=512,
            block_k=block_k, interpret=False, stairs=stairs)

    def backward(stairs, block_k):
        return lambda q, k, v, out, lse, g: _bwd(
            (q, k, v, out, lse), g, scale=128 ** -0.5, causal=stairs is None,
            block_q=512, block_k=block_k, interpret=False, stairs=stairs,
            g_lse=lse)

    for bh, t, t_kv, stairs, block_k in ((256, 2048, 2048, None, 512),
                                         (32, 16384, 1024, (2048, 128), 128)):
        q, kv = (_on(v5e[0], (bh, n, 128), jnp.bfloat16) for n in (t, t_kv))
        lse = _on(v5e[0], (bh, t), jnp.float32)
        call, = calls_of(forward(stairs, block_k), q, kv, kv)
        stated, used = _scoped_vmem(call)
        assert stated is None and used <= _VMEM_DEFAULT, (stated, used)
        call, = calls_of(backward(stairs, block_k), q, kv, kv, q, lse, q)
        result, operands = call.split(" custom-call(", 1)
        assert re.findall(r"\w+\[[\d,]+\]", result) == [
            f"bf16[{bh},{t},128]", f"bf16[{bh},{t_kv},128]",
            f"bf16[{bh},{t_kv},128]"]
        stated, used = _scoped_vmem(call)
        assert stated == _bwd_vmem_bytes(t, 128, 128, 512, block_k, 2, 1,
                                         t_kv)
        assert used <= stated <= 128 << 20, (used, stated)


def test_latent_expert_block_and_module_compile_for_v5e(v5e):
    """The block the latent-attention cell is made of, at a small size
    (its head widths, fewer heads, narrower layers): a dense layer, an
    expert layer that holds 4 of 16 experts beside a shared one, the
    multi-token-prediction module, recomputed in the backward pass.
    The flash kernels take 192 / 128 and the held experts lower to
    grouped-product kernels."""
    from horovod_tpu.models import (BlockSpec, LatentAttention,
                                    NextTokenModule, TopkExperts,
                                    Transformer, TransformerConfig,
                                    apply_with_aux, lm_loss)

    cfg = TransformerConfig(
        vocab_size=2048, n_layers=2, d_model=512, n_heads=4, d_ff=1024,
        d_expert=256, n_experts=16, experts_per_token=4, max_len=1024,
        rope_theta=32e6, leading_dense=1, remat=True,
        block=BlockSpec(
            norm="rms", positions="rope_pairs",
            attention=LatentAttention(q_rank=384, kv_rank=128, nope_dim=128,
                                      rope_dim=64, v_dim=128),
            ffn=TopkExperts(scoring="sigmoid", renormalize=True, scale=2.5,
                            shared=1, held=(4, 4))))
    model, module = Transformer(cfg), NextTokenModule(cfg)
    tokens = jax.ShapeDtypeStruct((2, 1024), jnp.int32)

    def init(key):
        params = model.init(key, jnp.zeros((1, 1024), jnp.int32))["params"]
        params["next_token"] = module.init(
            key, jnp.zeros((1, 1024, 512), cfg.dtype),
            jnp.zeros((1, 1024), jnp.int32), params["embed"]["embedding"],
            params["lm_head"]["kernel"])["params"]
        return params

    def loss(params, bias, tokens):
        logits, aux = apply_with_aux(model, params, tokens,
                                     router_bias=bias, next_token=module)
        return lm_loss(logits, tokens) + 0.3 * lm_loss(
            aux["next_token_logits"], jnp.roll(tokens, -1, axis=-1))

    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    on_chip = lambda tree: jax.tree.map(
        lambda a: _on(v5e[0], a.shape, a.dtype), tree)
    text = _compile(jax.value_and_grad(loss), on_chip(params),
                    _on(v5e[0], (2, 16), jnp.float32),
                    on_chip(tokens)).as_text()
    assert "bf16[8,1024,192]" in text
    assert text.count("%ragged-dot-none") >= 9
    assert "bf16[8192,512]" in text  # the buffer: N * min(k, count) rows


def test_layer_norm_compiles_for_v5e(v5e):
    from horovod_tpu.ops.pallas.layer_norm import layer_norm

    def fwd_bwd(x, gamma, beta):
        return jax.value_and_grad(lambda x, g, b: jnp.sum(layer_norm(
            x, g, b, 1e-6, False).astype(jnp.float32)),
            (0, 1, 2))(x, gamma, beta)

    d = LM["d_model"]
    text = _compile(fwd_bwd, _on(v5e[0], (B, T, d), jnp.bfloat16),
                    _on(v5e[0], (d,), jnp.float32),
                    _on(v5e[0], (d,), jnp.float32)).as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("shape", [
    (B, T, LM["vocab"]), chip_smoke.OLMOE_LOGITS[1],
    # two vocabularies of the models queued next, neither a multiple of
    # the column tile: the tail compiles, and the tile fits VMEM
    (1, 8192, 151936), (1, 8192, 201024),
    # a vocabulary of bytes under one tile, float32 logits, the rows of
    # a head of 8 outputs a position at 16384 positions
    (1, 16384, 8, 320)],
    ids=["lm", "olmoe", "v151936", "v201024", "bytes_8_outputs"])
def test_softmax_xent_compiles_for_v5e(v5e, shape):
    from horovod_tpu.ops.pallas.softmax_xent import softmax_xent

    def fwd_bwd(logits, labels):
        return jax.value_and_grad(lambda lg: jnp.mean(
            softmax_xent(lg, labels, False)))(logits)

    dtype = jnp.float32 if shape[-1] == 320 else jnp.bfloat16
    text = _compile(fwd_bwd, _on(v5e[0], shape, dtype),
                    _on(v5e[0], shape[:-1], jnp.int32)).as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("chips", [1, 4])
def test_lm_step_compiles_for_v5e(v5e, chips):
    """``chip_smoke.py``'s own training step — ``DistributedOptimizer``
    inside the framework's ``shard_map``, Pallas ``custom_vjp`` rules
    under it — at full width on one and on four described chips."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel import make_mesh

    mesh = make_mesh({"hvd": chips}, devices=v5e[:chips])
    model = chip_smoke.lm_model()
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))

    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), tokens)["params"]
    compiled = chip_smoke.lm_step(model, opt, mesh).lower(
        _shaped(mesh, params, P()),
        _shaped(mesh, jax.eval_shape(opt.init, params), P()),
        _shaped(mesh, tokens, P("hvd"))).compile()

    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == (chips > 1)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("kind", ["ring", "zigzag", "ulysses"])
def test_sequence_parallel_attention_compiles_for_v5e(v5e, kind):
    """The three sequence-parallel attentions with the flash kernel as
    their local block, forward and backward, over four described chips.
    A compile only: none of them has run on a chip."""
    import functools

    from horovod_tpu import parallel
    from horovod_tpu.ops.pallas.flash_attention import flash_attention
    from horovod_tpu.parallel._compat import shard_map

    body = {
        "ring": functools.partial(parallel.ring_attention,
                                  axis_name="sp", causal=True),
        "zigzag": functools.partial(parallel.zigzag_ring_attention,
                                    axis_name="sp"),
        "ulysses": functools.partial(parallel.ulysses_attention,
                                     axis_name="sp", causal=True,
                                     attn_fn=flash_attention),
    }[kind]
    mesh = parallel.make_mesh({"sp": 4}, devices=v5e)
    spec = P(None, "sp", None, None)
    attend = shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=spec)

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32)), (0, 1, 2))(q, k, v)

    qkv = [jax.ShapeDtypeStruct((2, 4 * T, H, D), jnp.bfloat16,
                                sharding=NamedSharding(mesh, spec))] * 3
    text = _compile(fwd_bwd, *qkv).as_text()
    assert "tpu_custom_call" in text
    moves = "all-to-all" if kind == "ulysses" else "collective-permute"
    assert moves in text


def test_topk_moe_compiles_for_v5e_as_grouped_product_kernels(v5e):
    """The dropless expert layer at OLMoE's widths and the benchmark
    cell's 16,384 tokens, forward and backward: the compiler lowers
    ``ragged_dot`` and both its gradients to kernels of its own (nine
    ``ragged-dot`` custom calls), not to a dense product over all 64
    experts, and the backward pass holds no scatter of rows."""
    from horovod_tpu.parallel.moe import moe_param_shapes, topk_moe

    def fwd_bwd(x, params):
        return jax.grad(lambda x, p: jnp.sum(
            topk_moe(x, p, k=8)[0].astype(jnp.float32)), (0, 1))(x, params)

    params = {name: {"kernel": _on(v5e[0], shape, jnp.float32)}
              for name, shape in moe_param_shapes(
                  2048, 1024, 64, gated=True).items()}
    text = _compile(fwd_bwd, _on(v5e[0], (16384, 2048), jnp.bfloat16),
                    params).as_text()
    products = [line for line in text.splitlines()
                if " custom-call(" in line and "%ragged-dot-none" in line
                and "tpu_custom_call" in line]
    assert len(products) == 9
    assert "bf16[131072,2048]" in text
    assert not [line for line in text.splitlines()
                if " scatter(" in line and "[131072,2048]" in line]


def _computations(text):
    """``{name: lines}`` of an optimized HLO module's computations."""
    computations, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
            computations[name] = []
        elif name is not None:
            computations[name].append(line)
    return computations


def _outside_loop_bodies(text):
    """The lines of an optimized HLO module that lie in no computation
    a ``while`` names as its body or condition, nor in one called from
    there (a fusion inside a loop body is its own computation)."""
    computations = _computations(text)
    called = {name: set(re.findall(
        r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", "\n".join(lines)))
        for name, lines in computations.items()}
    inside = set()
    todo = [m for lines in computations.values() for line in lines
            if " while(" in line
            for m in re.findall(r"(?:body|condition)=%?([\w.\-]+)", line)]
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo.extend(called.get(name, ()))
    return [line for name, lines in computations.items()
            if name not in inside for line in lines]


@pytest.fixture(scope="module")
def cell_step(v5e):
    """``cell_step(workload, transformer=None)``: a cell's step as
    ``benchmark/run.py`` builds it (the ``spmd`` loop's own step over
    the family's loss) at published widths and the cell's own batch,
    compiled for one described chip; with ``transformer``, the family is
    handed that class for the program's ``Transformer``; with ``depth``,
    those keys of the cell's configuration (its counts of layers) are
    replaced.  Each is compiled once for the tests of this file, and a
    cell at its own depth by one test only: ``cell_step.misses`` lists
    what was compiled.  A cell at its own depth is compiled as on the
    chip, whose memory the model asks for (``device_memory_bytes``
    stands at a v5e's limit); at another depth with no limit told, as
    the comparisons of instructions want it: the program of every
    backend that tells none.  ``cell_step.plan(workload, next_token)``
    is the ``kept_plan`` the model made at the cell's own depth."""
    import sys

    import optax

    import horovod_tpu.models
    from horovod_tpu.models import transformer as program
    from horovod_tpu.parallel import make_mesh

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests", "benchmark"))
    try:
        from benchmark_toy import load_by_path
    finally:
        sys.path.pop(0)
    bench = load_by_path(os.path.join(repo, "benchmark", "run.py"),
                         "hvd_benchmark_run_chip_compile")
    mesh = make_mesh({"hvd": 1}, devices=v5e[:1])
    compiled = {}

    def compile_step(workload, transformer=None, variant=None, depth=None,
                     limit=V5E_BYTES_LIMIT):
        """``variant`` names what the caller changed around the call: a
        step of its own in the cache.  ``limit``: what the device says
        it has at the cell's own depth (``None``: it tells none, and
        nothing is planned)."""
        depth = depth or {}
        limit = None if depth else limit
        key = (workload, transformer, variant, tuple(sorted(depth.items())),
               limit)
        if key in compiled:
            return compiled[key]
        compile_step.misses.append(key)
        cell = bench.load_cell(repo, workload)
        assert set(depth) <= set(cell.config), depth
        cell.config = {**cell.config, **depth}
        opt, step = cell.loop.make_step(
            cell, optax.adamw(**cell.job["optimizer"]["args"]), mesh)
        params, extra = jax.eval_shape(
            lambda key: cell.family.init(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct(
            (cell.job["per_chip_batch"], cell.job["seq_len"]), jnp.int32)
        was = horovod_tpu.models.Transformer, program.device_memory_bytes
        horovod_tpu.models.Transformer = transformer or was[0]
        program.device_memory_bytes = lambda: (limit, None)
        try:
            compiled[key] = step.lower(
                _shaped(mesh, params, P()), _shaped(mesh, extra, P()),
                _shaped(mesh, jax.eval_shape(opt.init, params), P()),
                _shaped(mesh, tokens, P("hvd"))).compile()
        finally:
            (horovod_tpu.models.Transformer,
             program.device_memory_bytes) = was
        return compiled[key]

    def plan(workload, next_token=False):
        cell = bench.load_cell(repo, workload)
        return program.kept_plan(
            cell.family._program_config(cell.config),
            cell.job["per_chip_batch"], cell.job["seq_len"], V5E_BYTES_LIMIT,
            next_token)

    compile_step.misses = []
    compile_step.plan = plan
    return compile_step


def _bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _fits_one_chip(compiled):
    return _bytes(compiled) < HBM_BYTES


def _filled_as_planned(compiled, plan, parent_gib, more_gib=0.0):
    """A recomputed cell's step fills the chip as its plan says: at most
    the line of 15.0 GiB and at least what it took at the parent of PR 54
    (``parent_gib``, compiled there, where one gradient tree was charged
    whole at the backward's first block and Laguna, JoyAI and Phi-4 kept
    nothing) and four fifths of what the plan keeps beyond that
    (``more_gib``); the plan keeps a name only under its budget, and its
    predicted peak, the largest of the step's moments, is no less than
    what the compiler found (the issue allowed it 0.3 GiB under; it is
    over in every cell: the plan holds each gradient from where it is
    made to the end, this step's fused Adam eats it there)."""
    gib = 2 ** 30
    assert (parent_gib + 0.8 * more_gib) * gib - 2 ** 20 <= _bytes(
        compiled) <= 15.0 * gib
    assert plan.budget == int(0.95 * V5E_BYTES_LIMIT)
    assert plan.peak <= plan.budget or not any(plan.names)
    assert plan.peak >= _bytes(compiled)
    return True


@pytest.mark.parametrize("held", ["clip", "accumulate", "tied"])
def test_a_step_that_holds_its_gradients_fits_its_plan(v5e, monkeypatch,
                                                       tmp_path, held):
    """A recomputed toy's step that holds its whole gradient tree,
    compiled for one described chip that has room for ONE layer's ``up``
    of two: the first layer keeps it, the second makes it again, and the
    step takes what the plan predicted, as a whole and moment by moment
    (``tests/xla_live.py`` reads what is live where off XLA's dump).
    ``clip``: under ``clip_by_global_norm`` ahead of Adam, which wants
    the whole tree before the first update; ``tied``: the same with the
    embedding for a head.  ``accumulate``: under
    ``DistributedOptimizer(backward_passes_per_step=2)``, whose
    accumulator is a fourth tree resident on the device; the plan is
    handed what the device would say is in use once the state is placed
    (``device_memory_bytes``' second number), and without it the same
    device would have been planned a tree short.

    Here the logits outweigh the parameters and a block's moment, so the
    plan's peak stands at the head, where the compiled step's does, and
    that moment is arithmetic: the plan's and at most 4% less.  As the
    backward pass enters its first block (the last layer), where the
    peak of every cell of the benchmark stands, the compiled step holds
    no more than the plan charges, and under a tied head the two things
    the plan charges there for the compiler's schedule alone are live
    through that whole block: the logits' gradient and the head's input,
    which wait for the head's weight gradient, the embedding's second
    use.  (A jaxlib that makes that product earlier fails here, and the
    two terms can go.  Under a head of its own the compiler is free:
    this step makes the product at once, the same toy with room for both
    layers' ``up`` makes it after block 1, so the plan charges it and
    nothing is held here.  Nor is the LAST block's moment: with room to
    spare the scheduler puts weight gradients off, and one block's
    leftovers stand in the next one's backward pass.)"""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import (Transformer, TransformerConfig, lm_loss,
                                    transformer)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import xla_live
    finally:
        sys.path.pop(0)
    batch, seq = 16, 1024
    cfg = TransformerConfig(vocab_size=8192, n_layers=2, d_model=512,
                            n_heads=4, d_ff=2048, max_len=seq, remat=True,
                            dtype=jnp.bfloat16, tie_head=held == "tied")
    model = Transformer(cfg)
    opt = (hvd.DistributedOptimizer(
        optax.adam(1e-3), named_axes=(), backward_passes_per_step=2)
        if held == "accumulate" else optax.chain(
            optax.clip_by_global_norm(1.0), optax.adam(1e-3)))
    tokens = _on(v5e[0], (batch, seq), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    placed = SingleDeviceSharding(v5e[0])
    params, state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed),
        (params, jax.eval_shape(opt.init, params)))
    in_use = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (params, state))) if held == "accumulate" else None
    up = transformer.kept_bytes(cfg, batch, seq, 0, ("mlp_up",))["mlp_up"]
    floor = transformer.kept_plan(cfg, batch, seq, 1, resident=in_use)
    assert floor.moment == "head"
    room = -(-(floor.peak + up + up // 2) * 20 // 19)
    monkeypatch.setattr(transformer, "device_memory_bytes",
                        lambda: (room, in_use))
    plan = transformer.kept_plan(cfg, batch, seq, room, resident=in_use)
    assert plan.names == (("mlp_up",), ()) and plan.peak == floor.peak + up
    if held == "accumulate":
        assert plan.resident == in_use > 4 * plan.params
        assert transformer.kept_plan(cfg, batch, seq, room).names == (
            ("mlp_up",),) * 2

    def step(params, state, tokens):
        grads = jax.grad(lambda p: lm_loss(
            model.apply({"params": p}, tokens), tokens))(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    compiled = xla_live.compile_with_dump(jax.jit(
        step, donate_argnums=(0, 1)).lower(params, state, tokens), tmp_path)
    again = _products(_recomputed(compiled.as_text(), "/mlp/up/"))
    assert len(again) == 1 and "block_1" in again[0]
    assert 0.9 * plan.peak < _bytes(compiled) <= plan.peak
    # moment by moment
    dump = xla_live.Dump(tmp_path)
    windows, found, want = dump.windows(), dump.moments(), dict(plan.moments)
    assert list(windows) == ["head", "block 1", "block 0"]
    assert dump.arguments <= plan.resident + tokens.size * 4 + 2 ** 16
    assert max(found, key=found.get) == plan.moment == "head"
    # (what the step is short of the head's moment lies in VMEM at these
    # sizes: the head's input and two of a row's statistics)
    assert 0.96 * want["head"] <= found["head"][0] <= want["head"]
    assert found["block 1"][0] <= want["block 1"]
    # what waits for the head's weight gradient
    x = batch * seq * cfg.d_model * 2
    waiting = {"transpose(jvp(loss))/": x * cfg.vocab_size // cfg.d_model,
               "/jvp(Transformer)/ln_f/": x}
    for scope, size in waiting.items() if held == "tied" else ():
        (first, last), = [at[2:] for name, at in dump.heap.items()
                          if at[1] == size and scope in dump.source(name)]
        assert first < windows["head"][1] and last >= windows["block 0"][0]


def test_dense_cell_step_compiles_for_v5e(cell_step):
    """``gpt2_medium-spmd-1chip`` at its 24 layers and the cell's 8
    sequences of 1024, nothing recomputed: fits one chip; a flash forward
    and the one backward kernel a block, q, k and v ``[128, 1024, 64]``
    (8 sequences' 16 heads); the loss kernels once.  (The cell two
    ``perf_opt`` PRs claimed in: its memory and kernels are held here, as
    the other cells' are by the tests below.)"""
    compiled = cell_step("gpt2_medium-spmd-1chip")
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if " custom-call(" in line and "tpu_custom_call" in line]
    assert len([line for line in kernels
                if "[128,1024,64]" in line]) == 24 * 2
    # and a block's two layer norms, forward and backward; the last
    # norm's two; softmax-xent's two
    assert len(kernels) == 24 * (2 + 4) + 2 + 2
    assert "rematted_computation" not in text
    assert _fits_one_chip(compiled)


@pytest.mark.parametrize("workload,grouped_products,kernels", [
    # nine grouped products, two flash kernels, softmax-xent's two
    ("olmoe_1b_7b-spmd-1chip", 9, 13),
    # five expert layers' grouped products, forward, recomputed and
    # backward, and the four buffers a layer that nobody writes; six
    # blocks' flash kernels, each once; softmax-xent twice
    ("joyai_llm_flash-spmd-1chip", 5 * 9, 5 * (9 + 4) + 6 * 2 + 4)],
    ids=["olmoe_1b_7b", "joyai_llm_flash"])
def test_sparse_cell_step_compiles_for_v5e(cell_step, workload,
                                           grouped_products, kernels):
    """A sparse cell at published widths and the cell's batch of 4
    sequences of 4096: fits one chip."""
    compiled = cell_step(workload)
    text = compiled.as_text()
    assert text.count("%ragged-dot-none") >= grouped_products
    assert text.count("tpu_custom_call") >= kernels
    loops = [line for line in text.splitlines() if " while(" in line
             and "/moe/" in line]
    whole = [line for line in _outside_loop_bodies(text)
             if " gather(" in line
             and line.split(" = ", 1)[1].startswith(("bf16[131072,2048]",
                                                     "f32[131072,2048]"))]
    if workload.startswith("joyai"):
        # the held experts' passes have the extent of the rows that
        # exist: loops, and no gather of the whole buffer beside them
        assert loops and not whole
        # a recomputed block does not run its forward kernel again:
        # forward and backward once a block, each taking q and k as
        # [B H, T, d_qk]
        assert len([line for line in text.splitlines()
                    if "tpu_custom_call" in line
                    and "[128,4096,192]" in line]) == 6 * 2
        # nor the output projection and latent attention's two narrow
        # first products, whose results it keeps; q and k at 192 are
        # wider than the kernel's output, so ``q_b`` and ``kv_b`` run
        # again
        assert not _products(_recomputed(
            text, "/attn/out/", "/attn/latent/q_a/", "/attn/latent/kv_a/"))
        # the plan, moment by moment: the dense layer's pair, the five
        # shared experts' (the module's among them) and block 0's ``q_b``
        # are kept, 0.86 GiB; the other five ``q_b``, every ``kv_b`` and
        # the held experts' products run again
        plan = cell_step.plan(workload, next_token=True)
        pair = ("mlp_gate", "mlp_up")
        assert list(plan.names) == [pair + ("latent_q_b",)] + [pair] * 5
        assert plan.moment == "block 5"
        assert len(_products(_recomputed(text, "/attn/latent/q_b/"))) == 5
        assert len(_products(_recomputed(text, "/attn/latent/kv_b/"))) == 6
        assert not _products(_recomputed(text, "/mlp/gate/", "/mlp/up/",
                                         "/moe/shared/"))
        assert _filled_as_planned(compiled, plan, 13.873, 0.86)
        # nor what its routing decided: of the five routed blocks the
        # router's product and no sort (``top_k``'s over [N, E], the
        # slots') in the recomputation; the weights are read off the
        # scores by a comparison that writes no [N, k, E], and no float
        # is gathered or scattered one by one
        again = _recomputed(text, "/moe/route/", "/moe/dispatch/")
        assert len(_products(again)) == 5
        assert not [line for line in again if " sort(" in line]
        sorts = [line for line in text.splitlines() if " sort(" in line
                 and ("/moe/route/" in line or "(argsort)" in line)]
        assert len(sorts) == 5 * 2
        assert not [line for line in _outside_loop_bodies(text)
                    if re.match(r"\s*(ROOT )?%[\w.\-]+ = f32\[8,16384,256\]",
                                line) and " fusion(" in line]
        assert "take_along_axis" not in text
    else:
        # every row exists: the three plain gathers, no loop, no scatter
        assert whole and not loops
        assert "bf16[131072,2048]" in text
        assert not [line for line in text.splitlines()
                    if " scatter(" in line and "[131072,2048]" in line]
    assert _fits_one_chip(compiled)


def test_looped_cell_step_compiles_for_v5e_as_one_set_of_block_bodies(
        cell_step):
    """``ouro_2_6b-spmd-1chip`` (8 blocks run 4 times, one sequence of
    4096, every block application recomputed): the passes are a scan,
    so the step holds the kernels of N = 8 block bodies, not of R N =
    32: a flash forward a block in the forward loop; the one backward
    kernel a block in the backward loop and NO forward kernel there (its
    output and lse are saved, stacked over the passes); the loss kernels
    once over all four exits."""
    compiled = cell_step("ouro_2_6b-spmd-1chip")
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "[16,4096,128]" in line]
    assert len(flash) == 8 * 2
    assert text.count("tpu_custom_call") == 8 * 2 + 2
    assert len([line for line in text.splitlines()
                if " while(" in line]) == 2
    inside = len(flash) - len([line for line in _outside_loop_bodies(text)
                               if "tpu_custom_call" in line
                               and "[16,4096,128]" in line])
    assert inside == 8 * 2
    # what the backward loop reads in the recomputed kernel's place
    assert "bf16[4,16,4096,128]" in text and "f32[4,16,4096]" in text
    # one head product over the 16,384 rows of the four exits
    assert "bf16[16384,49152]" in text
    assert _fits_one_chip(compiled)


@pytest.mark.parametrize("heads,groups,d,d_v,window", [
    (72, 8, 128, 128, 512), (48, 8, 128, 128, None), (64, 16, 64, 64, None),
    (40, 20, 64, 128, None), (40, 20, 64, 128, 512)],
    ids=["sliding-72over8", "full-48over8", "full-64over16-heads-of-64",
         "full-40over20-64-and-128", "sliding-40over20-64-and-128"])
def test_flash_backward_with_grouped_heads_within_the_v5e_vmem(
        v5e, heads, groups, d, d_v, window):
    """The backward pass at ``laguna_s_2_1-spmd-1chip``'s two kernel
    shapes (T 8192, heads of 128, 72 or 48 query heads over 8 key-value
    heads, a window of 512 or none) and at ``lfm2_24b_a2b-spmd-1chip``'s
    (two sequences' 32 query heads of 64 over their 8 key-value heads)
    and at ``phi4_mini_flash-spmd-1chip``'s (two sequences' 20 query heads
    of 64 over their 10 key-value heads with values of 128, the first
    shape whose values are WIDER than its keys, with a window of 512 and
    without):
    ONE custom call that takes q as ``[H, T, d]`` and k and v as ``[G,
    T, d]``, never repeated to q's heads, and gives dq with q's heads
    and dk, dv with G, each the sum over its group formed in float32 in
    VMEM.  It holds q, dO, dq and the float32 dq of a whole head and the
    float32 dk and dv of a whole key-value head: the call states that
    from its own blocks and the kernel's compiler takes no more."""
    from horovod_tpu.ops.pallas.flash_attention import _bwd, _bwd_vmem_bytes

    t = 8192

    def bwd(q, k, v, out, lse, g):
        return _bwd((q, k, v, out, lse), g, scale=d ** -0.5, causal=True,
                    block_q=512, block_k=512, interpret=False, window=window)

    q, k, v, out = (_on(v5e[0], (h, t, w), jnp.bfloat16) for h, w in (
        (heads, d), (groups, d), (groups, d_v), (heads, d_v)))
    text = _compile(bwd, q, k, v, out,
                    _on(v5e[0], (heads, t), jnp.float32), out).as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1
    wide, narrow = f"bf16[{heads},{t},{d}]", f"bf16[{groups},{t},{d}]"
    values, outs = f"bf16[{groups},{t},{d_v}]", f"bf16[{heads},{t},{d_v}]"
    result, operands = calls[0].split(" custom-call(", 1)
    assert re.findall(r"\w+\[[\d,]+\]", result) == [wide, narrow, values]
    assert operands.split("operand_layout_constraints={", 1)[1].startswith(
        f"{wide}{{2,1,0}}, {narrow}{{2,1,0}}, {values}{{2,1,0}}, "
        f"{outs}{{2,1,0}}")
    stated, used = _scoped_vmem(calls[0])
    assert stated == _bwd_vmem_bytes(t, d, d_v, 512, 512, 2,
                                     heads // groups)
    assert used <= stated <= 128 << 20


def test_window_and_full_layer_cell_step_compiles_for_v5e(cell_step):
    """``laguna_s_2_1-spmd-1chip`` at published widths and the cell's one
    sequence of 8192, every block recomputed: fits one chip; a flash
    forward and ONE backward kernel a block and no forward kernel in the
    recomputation (its output and lse are saved); q of a sliding layer
    ``[72, 8192, 128]``, of a full layer ``[48, 8192, 128]``, and in
    the k and v places ``[8, 8192, 128]``: no array of k or v has the
    query heads' count, and nothing broadcasts one to it."""
    compiled = cell_step("laguna_s_2_1-spmd-1chip")
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line
             and "/flash/" in line]
    narrow = "bf16[8,8192,128]{2,1,0}"
    seen = []
    for line in flash:
        operands = line.split("operand_layout_constraints={", 1)[1]
        q, k, v = re.findall(r"bf16\[[\d,]+\]\{2,1,0\}", operands)[:3]
        assert k == v == narrow, operands[:200]
        heads = int(re.match(r"bf16\[(\d+),8192,128\]", q).group(1))
        seen.append((heads, "window" if "/attn/window/" in line else "global",
                     "bwd" if "jit(_bwd)" in line else "fwd"))
        assert "rematted_computation" not in line
    assert sorted(seen) == sorted(
        [(48, "global", way) for way in ("fwd", "bwd")] * 2
        + [(72, "window", way) for way in ("fwd", "bwd")] * 3)
    # k and v are never made as wide as q
    assert not [line for line in text.splitlines()
                if " broadcast(" in line and re.search(
                    r"= bf16\[(1,)?(72|48),8192,128\]", line)]
    assert not re.search(r"bf16\[(1,)?8192,8,(6|9),128\]", text)
    # the held experts' grouped products, forward, recomputed, backward
    assert text.count("%ragged-dot-none") >= 4 * 9
    # a block keeps the kernel's q, k and v and the sum after attention:
    # the recomputation makes no projection of attention, no rotation and
    # no copy into the kernel's layout again.  Left under the
    # projections' scopes are their weights' casts to bfloat16, which the
    # backward products read, and under ``rope`` the tables ``[T, D]`` the
    # backward turn reads (``turn`` keeps nothing else): no array with
    # heads in it.  The plan, moment by moment: every SwiGLU's pair
    # (the dense layer's, the shared experts') and the held experts'
    # ``gate`` and ``up`` are kept, 1.50 GiB; the experts' ``down`` runs
    # again
    plan = cell_step.plan("laguna_s_2_1-spmd-1chip")
    pair = ("mlp_gate", "mlp_up")
    assert list(plan.names) == [pair] + [pair + ("moe_gate", "moe_up")] * 4
    assert plan.moment == "block 4"
    assert not _products(_recomputed(text, "/mlp/", "/moe/shared/"))
    # (a grouped product carries no scope: three forward, ``down`` again,
    # six backward in each of the four routed layers)
    assert len(_grouped_products(text)) == 4 * 10
    ahead = _recomputed(
        text, "/attn/window/q/", "/attn/window/kv/", "/attn/window/out/",
        "/attn/global/q/", "/attn/global/kv/", "/attn/global/out/")
    assert ahead and all('/convert_element_type"' in line for line in ahead)
    assert not _recomputed(text, "/flash/")
    assert not [line for line in _recomputed(text, "/rope/") if re.search(
        r"\[(1,)?(8192,(72|48|8)|(72|48|8),8192),\d+\]", line)]
    assert _fits_one_chip(compiled)
    assert _filled_as_planned(compiled, plan, 12.849, 1.50)


def test_conv_and_attention_cell_step_compiles_for_v5e(cell_step):
    """``lfm2_24b_a2b-spmd-1chip`` at published widths and the cell's
    two sequences of 8192, every block recomputed and keeping the
    results of all its products, for which a v5e has room (10.9 GiB
    where it took 8.3); ONE flash forward and ONE backward kernel, the
    attention layer's, q ``[64, 8192, 64]`` over k and v ``[16, 8192,
    64]`` (two sequences' 32 query heads over their 8 key-value heads,
    never repeated to q's count) and no forward kernel in the
    recomputation; the four conv mixers under their scopes, forward and
    backward, with no kernel and no convolution primitive of their own:
    the taps are elementwise; a conv block's recomputation makes
    neither ``in``, whose result the plan keeps, nor ``out``, whose
    result is in the kept sum: the gates and the taps alone."""
    compiled = cell_step("lfm2_24b_a2b-spmd-1chip")
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line
             and "/flash/" in line]
    assert len(flash) == 2
    for line in flash:
        assert "/block_1/attn/attn/global/flash/" in line
        assert "rematted_computation" not in line
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert re.findall(r"bf16\[[\d,]+\]\{2,1,0\}", operands)[:3] == [
            "bf16[64,8192,64]{2,1,0}", "bf16[16,8192,64]{2,1,0}",
            "bf16[16,8192,64]{2,1,0}"]
    assert sorted("bwd" if "jit(_bwd)" in line else "fwd"
                  for line in flash) == ["bwd", "fwd"]
    # k and v are never made as wide as q
    assert not [line for line in text.splitlines()
                if " broadcast(" in line and re.search(
                    r"= bf16\[(1,)?(2,)?(64|32),8192,64\]", line)]
    assert not re.search(r"bf16\[(2,)?8192,8,4,64\]", text)
    # every conv mixer, forward and backward, by its scopes
    for block in (0, 2, 3, 4):
        for scope in ("in", "gate_conv", "out"):
            path = f"/block_{block}/mixer/mixer/conv/{scope}/"
            assert f"jvp(Transformer)){path}" in text or (
                f"jvp(Transformer)/checkpoint{path}" in text), path
        again = _recomputed(text, f"/block_{block}/mixer/mixer/conv/")
        assert again and not _products(again)
    # the taps are shifted multiply-adds: no convolution under their scope
    assert not [line for line in text.splitlines()
                if " convolution(" in line and "/gate_conv/" in line]
    assert "/attn/global/qk_norm/" in text
    # the held experts' grouped products, forward, recomputed, backward
    assert text.count("%ragged-dot-none") >= 4 * 9
    # the plan: every product's result, 3.97 GiB; of the feed-forwards
    # and the experts nothing runs again but the router's small product
    # and the passes that move rows
    plan = cell_step.plan("lfm2_24b_a2b-spmd-1chip")
    experts = ("moe_gate", "moe_up", "moe_down")
    assert list(plan.names) == [
        ("mixer_in", "mlp_gate", "mlp_up"), experts] + [
            ("mixer_in",) + experts] * 3
    # (a grouped product carries no scope: three forward and six
    # backward in each of the four routed layers, none again)
    assert not _products(_recomputed(text, "/mlp/"))
    assert len(_grouped_products(text)) == 4 * 9
    assert len(_products(_recomputed(text, "/moe/route/"))) == 4
    assert _fits_one_chip(compiled)
    assert plan.moment == "block 4"
    assert _filled_as_planned(compiled, plan, 10.948)


def test_state_space_and_differential_cell_step_compiles_for_v5e(cell_step):
    """``phi4_mini_flash-spmd-1chip`` at published widths and the cell's
    two sequences of 8192, every block recomputed: fits one chip under
    the issue's line of 15.0 GiB; the three attention layers' two calls
    each, forward and ONE backward kernel a call, q ``[40, 8192, 64]``
    over k ``[20, 8192, 64]`` and values ``[20, 8192, 128]`` (two
    sequences' 20 pairs of query heads over their 10 pairs of key-value
    heads, never repeated to q's count), none in the recomputation; the
    cross layer (block 5) has no k/v projection and its backward kernels'
    dk and dv reach block 3's; the two Mamba layers' scans are two
    kernels each under their scope, one in the forward pass and ONE in the
    backward, none in the recomputation (the scan's output and entry
    states are kept) and no loop: they take ``c``, ``delta`` and ``dy``
    as ``[2, 8192, 5120]`` where they lie, with no ``[B, T, d_inner, N]``
    of states anywhere in the step; LayerNorm's kernel at 2560;
    the loss kernels at 25,008 columns, which no tile divides."""
    compiled = cell_step("phi4_mini_flash-spmd-1chip")
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line
             and "/flash/" in line]
    seen = []
    for line in flash:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert re.findall(r"bf16\[[\d,]+\]\{2,1,0\}", operands)[:3] == [
            "bf16[40,8192,64]{2,1,0}", "bf16[20,8192,64]{2,1,0}",
            "bf16[20,8192,128]{2,1,0}"]
        assert "rematted_computation" not in line
        kind = re.search(r"/block_(\d)/attn/attn/(\w+)/flash/", line)
        seen.append((int(kind.group(1)), kind.group(2),
                     "bwd" if "jit(_bwd)" in line else "fwd"))
    assert sorted(seen) == sorted(
        [(block, kind, way) for block, kind in (
            (1, "window"), (3, "global"), (5, "cross"))
         for way in ("fwd", "bwd")] * 2)
    # k and v are never made as wide as q, and the cross layer projects none
    assert not [line for line in text.splitlines()
                if " broadcast(" in line and re.search(
                    r"= bf16\[(1,)?(2,)?(40|20),8192,(64|128)\]", line)
                and "/flash/" in line]
    assert "/block_5/attn/attn/cross/q/" in text
    assert "/block_5/attn/attn/cross/kv/" not in text
    assert "/attn/cross/diff/" in text and "/attn/window/diff/" in text
    # the scans: a forward and ONE backward kernel a layer under the
    # scope, none recomputed, on the operands as they lie in HBM; no
    # loop, no [B, T, d, N] states
    for block in (0, 2):
        for scope in ("in", "conv", "proj", "scan", "gate_out"):
            assert f"/block_{block}/mixer/mixer/ssm/{scope}/" in text, scope
        calls = [line for line in text.splitlines()
                 if f"/block_{block}/mixer/mixer/ssm/scan/" in line
                 and " custom-call(" in line and "tpu_custom_call" in line]
        assert sorted(("jvp(" in line, "transpose(" in line)
                      for line in calls) == [(True, False), (True, True)]
        assert not [line for line in calls if "rematted_computation" in line]
        for line in calls:
            operands = line.split("operand_layout_constraints={", 1)[1]
            assert "bf16[2,8192,5120]{2,1,0}, f32[2,8192,5120]{2,1,0}" in (
                operands)
    assert "/block_4/mixer/mixer/gmu/" in text
    assert " while(" not in text
    assert not re.search(r"\[(2,)?8192,(2,)?(5120,16|16,5120)\]", text)
    assert not re.search(r"\[(2,)?(5120,16|16,5120),(2,)?8192\]", text)
    kernels = [line for line in text.splitlines()
               if " custom-call(" in line and "tpu_custom_call" in line]
    assert [line for line in kernels if "bf16[16384,2560]" in line
            and re.search(r"%ln\w*", line)]
    assert len([line for line in kernels if "[16384,25008]" in line]) == 2
    # the head is the embedding: no parameter of a head's shape
    assert "f32[2560,25008]" not in text
    # the plan, moment by moment: the three mixers' first products and
    # block 0's SwiGLU pair are kept, 1.41 GiB; the other five pairs run
    # again
    plan = cell_step.plan("phi4_mini_flash-spmd-1chip")
    assert list(plan.names) == [
        ("mixer_in", "mlp_gate", "mlp_up"), (), ("mixer_in",), (),
        ("mixer_in",), ()]
    assert plan.moment == "block 2"
    assert not _products(_recomputed(text, "/mixer/ssm/in/", "/mixer/gmu/in/"))
    for name in ("gate", "up"):
        assert len(_products(_recomputed(text, f"/mlp/{name}/"))) == 5
    assert _filled_as_planned(compiled, plan, 13.145, 1.41)
    # no more memory than with the scans as loops (13.614 GiB at the
    # parent of PR 47), read where no plan fills the chip: the step of a
    # device that tells no limit, every block on rung 0
    bare = cell_step("phi4_mini_flash-spmd-1chip", limit=None)
    assert len(_products(_recomputed(bare.as_text(), "/mlp/gate/"))) == 6
    assert _bytes(bare) <= 13.614 * 2 ** 30 < _bytes(compiled)


def test_chunk_summary_cell_step_compiles_for_v5e(cell_step):
    """``evabyte_6_5b-spmd-1chip`` at published widths, four layers and
    the cell's one sequence of 16384, every block recomputed: under the
    issue's line of 15.0 GiB; in every layer two forward and two backward
    flash calls under ``attn/eva/flash``, the local part on ``[256, 2048,
    128]`` (8 windows x 32 heads as batch rows) and the remote part on q
    ``[32, 16384, 128]`` over the summaries ``[32, 1024, 128]``, none in
    the recomputation (``out`` and ``lse`` are kept); the pooling and the
    join under their scopes and no kernel; the residual stream float32
    between the blocks; the head's logits float32 and the loss kernels
    on 131,072 rows of 320 columns."""
    compiled = cell_step("evabyte_6_5b-spmd-1chip")
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if " custom-call(" in line and "tpu_custom_call" in line]
    flash = [line for line in kernels if "/attn/eva/flash/" in line]
    seen = []
    for line in flash:
        assert "rematted_computation" not in line
        operands = line.split("operand_layout_constraints={", 1)[1]
        part = re.search(r"/block_(\d)/attn/attn/eva/flash/(\w+)/", line)
        shapes = re.findall(r"bf16\[[\d,]+\]\{2,1,0\}", operands)[:3]
        assert shapes == {
            "local": ["bf16[256,2048,128]{2,1,0}"] * 3,
            "remote": ["bf16[32,16384,128]{2,1,0}"]
            + ["bf16[32,1024,128]{2,1,0}"] * 2}[part.group(2)]
        seen.append((int(part.group(1)), part.group(2),
                     "bwd" if "jit(_bwd)" in line else "fwd"))
    assert sorted(seen) == sorted(
        (block, part, way) for block in range(4)
        for part in ("local", "remote") for way in ("fwd", "bwd"))
    for scope in ("qkv", "rope", "pool", "flash/join", "out"):
        assert f"/block_3/attn/attn/eva/{scope}/" in text, scope
    assert not [line for line in kernels if "/attn/eva/pool/" in line
                or "/attn/eva/flash/join/" in line]
    # the stream between the blocks, and the sum after attention, float32
    assert "f32[1,16384,4096]" in text and "bf16[1,16384,4096]" in text
    # the loss: a forward and a backward kernel on float32 rows
    loss = [line for line in kernels if "f32[131072,320]" in line]
    assert len(loss) == 2 and all("(loss)" in line for line in loss)
    assert "f32[4096,2560]" in text
    assert " while(" not in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            ) <= 14.886 * 2 ** 30 + 2 ** 20 < 15.0 * 2 ** 30


def test_selective_scan_is_two_kernels_within_the_default_vmem_scope(v5e):
    """The scan alone at the cell's shape, ``[2, 8192, 5120]`` x 16 in
    bfloat16 beside a float32 ``delta``, forward and backward: ONE custom
    call each, on ``c``, ``delta`` and ``dy`` as they lie in HBM (no copy
    of an operand to another layout or dtype around a call: the large
    arrays of the program are the kernels' own operands and results) and
    within the scope a call gets that asks for none: the backward kernel
    holds a T block's states for its channels (4 MiB at 64 x 16 x 1024)
    beside its blocks."""
    from horovod_tpu.ops.pallas.flash_attention import _VMEM_DEFAULT
    from horovod_tpu.ops.selective_scan import selective_scan

    batch, t, d, n = 2, 8192, 5120, 16

    def both(*args):
        return jax.grad(lambda *a: jnp.sum(selective_scan(*a).astype(
            jnp.float32)), range(6))(*args)

    wide, narrow = (_on(v5e[0], (batch, t, width), jnp.bfloat16)
                    for width in (d, n))
    text = _compile(both, wide, _on(v5e[0], (batch, t, d), jnp.float32),
                    _on(v5e[0], (d, n), jnp.float32), narrow, narrow,
                    _on(v5e[0], (d,), jnp.float32)).as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2
    for call in calls:
        stated, used = _scoped_vmem(call)
        assert stated is None and used <= _VMEM_DEFAULT, (stated, used)
        operands = call.split("operand_layout_constraints={", 1)[1]
        assert "bf16[2,8192,5120]{2,1,0}, f32[2,8192,5120]{2,1,0}" in operands
    # nothing makes an array of the operands' size around the calls
    assert not [line for line in text[text.index("ENTRY"):].splitlines()
                if re.search(r"= (bf16|f32)\[2,8192,(5120|40,128)\]", line)
                and re.search(r" (copy|fusion|transpose|convert|reshape)\(",
                              line)]
    assert not re.search(r"\[(2,)?8192,(2,)?(5120,16|16,5120)\]", text)


def _results_in_memory(text, *scopes):
    """``[(result types, line)]`` of a compiled step's instructions under
    one of ``scopes`` whose results are arrays in HBM: those of the
    entry and of loop bodies, not what a fusion holds inside."""
    computations = _computations(text)
    inside = {m for lines in computations.values() for line in lines
              if " fusion(" in line or "to_apply=" in line
              for m in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line)}
    found = []
    for name, lines in computations.items():
        for line in lines if name not in inside else ():
            result = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(", line)
            scope = re.search(r'op_name="([^"]*)"', line)
            if result and scope and any(s in scope.group(1) for s in scopes):
                found.append((re.findall(r"\w+\[[\d,]*\]", result.group(1)),
                              line))
    return found


@pytest.mark.parametrize("workload,scopes,whole,pieces,operands", [
    ("laguna_s_2_1-spmd-1chip",
     ("/rope/", "/attn/window/q/", "/attn/window/kv/", "/attn/global/q/",
      "/attn/global/kv/"),
     ("f32[1,8192,72,128]", "f32[1,8192,48,128]"),
     (r"72,64\]", r"48,64\]", r"48,32\]", r"8,64\]", r"8,32\]"),
     {"bf16[72,8192,128]", "bf16[48,8192,128]", "bf16[8,8192,128]"}),
    ("joyai_llm_flash-spmd-1chip", ("/attn/latent/",),
     ("f32[4,4096,32,192]",), (r"32,32,1\]", r"32,32,2\]"),
     {"bf16[128,4096,192]", "bf16[128,4096,128]"})],
    ids=["laguna_s_2_1", "joyai_llm_flash"])
def test_rotary_turn_is_a_pass_over_whole_heads_in_the_cell_step(
        cell_step, workload, scopes, whole, pieces, operands):
    """The rotation of q and k (``turn``: ``x c + (x P) s``) in a cell's
    step, the one the tests above compiled: nothing under ``rope`` or a
    projection's scope writes q in float32 (the projection's result is
    the activation dtype's, not a float32 array the turn then reads) and
    nothing writes a piece of a head (a half, a quarter, a pair), forward
    or backward; the flash kernels still take ``[B H, T, d]`` in
    bfloat16."""
    text = cell_step(workload).as_text()
    written = _results_in_memory(text, *scopes)
    assert len(written) > 50
    for results, line in written:
        assert not set(results) & set(whole), line[:300]
        assert not [r for r in results for piece in pieces
                    if re.search(piece, r)], line[:300]
    # the turn is there, by its product, forward and backward
    assert [line for _, line in written if '/dot_general"' in line
            and ("/rope/" in line or "/attn/latent/dot_general" in line)
            and "transpose(jvp(" in line]
    seen = set()
    for line in text.splitlines():
        if " custom-call(" in line and "tpu_custom_call" in line and (
                "jit(_fwd)" in line or "jit(_bwd)" in line):
            q, k, v = re.findall(r"(bf16\[[\d,]+\])\{2,1,0\}", line.split(
                "operand_layout_constraints={", 1)[1])[:3]
            seen |= {q, k, v}
    assert seen == operands


def _recomputed(text, *scopes):
    """The instructions of a compiled step that a checkpoint makes again
    (``rematted_computation`` in their ``op_name``) under one of
    ``scopes``."""
    return [line for line in text.splitlines()
            if "rematted_computation" in line
            and any(scope in line for scope in scopes)]


def _products(lines):
    """Those of ``lines`` that are a matrix product's instruction (a
    fusion it went into carries the same ``op_name`` beside it)."""
    return [line for line in lines
            if " convolution(" in line and '/dot_general"' in line]


def _grouped_products(text):
    """The grouped products' instructions of a compiled step (the
    ``%ragged-dot-none`` custom calls, not the metadata calls beside
    them)."""
    return re.findall(r"^\s*(?:ROOT )?%ragged-dot-none[.\d]* = .*$", text,
                      re.M)


def _instructions(compiled):
    """The optimized program's instructions without what names a source
    line: metadata, the tables of files and functions ahead of the
    computations, and the kernels' serialized bodies."""
    out = []
    for line in compiled.as_text().splitlines():
        if not line.startswith((" ", "ENTRY", "%", "}", "ROOT")):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        if "tpu_custom_call" in line:
            line = re.sub(r"backend_config=.*$", "", line)
        out.append(line)
    return out


# What the comparisons below compile both of their sides at: published
# widths and the cell's batch, and the least depth that has a layer of
# each kind the cell has.  A step has the instructions a change added or
# it has not, at 2 layers as at 24; the cell's own depth is compiled
# once, by the test that reads its memory.
SHALLOW = {
    "gpt2_medium-spmd-1chip": {"n_layer": 2},       # a block after a block
    # the leading dense layer and one layer of experts (and the module
    # that predicts the token after next, which is no layer of these)
    "joyai_llm_flash-spmd-1chip": {"num_hidden_layers": 2},
    "olmoe_1b_7b-spmd-1chip": {},                   # one layer as it is
    # one block, run ``total_ut_steps`` = 4 times as published
    "ouro_2_6b-spmd-1chip": {"num_hidden_layers": 1},
}


@pytest.mark.parametrize("workload,flash,blocks,loops,kinds", [
    ("gpt2_medium-spmd-1chip", "[128,1024,64]", 2, 0,
     ("/block_0/mlp/", "/block_1/mlp/")),
    # the dense layer, the layer of experts, and the module's block
    ("joyai_llm_flash-spmd-1chip", "[128,4096,192]", 3, None,
     ("/block_0/mlp/", "/block_1/moe/", "/block/moe/")),
    # one block under the forward loop over the passes and the backward
    ("ouro_2_6b-spmd-1chip", "[16,4096,128]", 1, 2, ("/block_0/mlp/",))],
    ids=["gpt2_medium", "joyai_llm_flash", "ouro_2_6b"])
def test_shallow_steps_have_a_layer_of_each_kind(cell_step, workload, flash,
                                                 blocks, loops, kinds):
    """``SHALLOW`` is what it says: at that depth the step (the one the
    comparisons below take for the program as it is) has every kind of
    layer the cell has, a flash forward and the one backward kernel a
    block at the cell's own shape, and the loop over the passes where
    the cell has one."""
    text = cell_step(workload, depth=SHALLOW[workload]).as_text()
    kernels = [line for line in text.splitlines()
               if " custom-call(" in line and "tpu_custom_call" in line]
    assert len([line for line in kernels if flash in line]) == blocks * 2
    assert all(kind in text for kind in kinds)
    if loops is not None:
        assert len([line for line in text.splitlines()
                    if " while(" in line]) == loops


@pytest.mark.parametrize("workload", ["gpt2_medium-spmd-1chip",
                                      "joyai_llm_flash-spmd-1chip"])
def test_cells_without_passes_compile_to_the_step_from_before(cell_step,
                                                              workload):
    """With one pass, no sandwich norm and no gate the compiled step is
    the one the model gave before it had them (``TransformerBefore``:
    its ``__call__`` written out), instruction for instruction."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from test_transformer_looped import TransformerBefore
    finally:
        sys.path.pop(0)
    depth = SHALLOW[workload]
    now = _instructions(cell_step(workload, depth=depth))
    before = _instructions(cell_step(workload, TransformerBefore,
                                     depth=depth))
    assert len(now) > 2000 and len(now) == len(before)
    assert now == before


@pytest.mark.parametrize("workload", ["gpt2_medium-spmd-1chip",
                                      "olmoe_1b_7b-spmd-1chip"])
def test_saved_names_are_nothing_in_a_step_without_recomputation(
        cell_step, workload, monkeypatch):
    """The flash forward rule names its output and lse for the policy
    of a recomputed block, a routed layer what its routing decided; a
    step that recomputes nothing compiles to the step without the names,
    instruction for instruction."""
    depth = SHALLOW[workload]
    named = _instructions(cell_step(workload, depth=depth))
    # (the package's attribute of this name is the function)
    for module in ("horovod_tpu.ops.pallas.flash_attention",
                   "horovod_tpu.models.transformer",
                   "horovod_tpu.parallel.moe"):
        monkeypatch.setattr(importlib.import_module(module),
                            "checkpoint_name", lambda x, name: x)
    unnamed = _instructions(cell_step(workload, variant="unnamed",
                                      depth=depth))
    assert len(named) > 2000 and named == unnamed


def test_looped_cell_step_keeps_what_it_kept(cell_step, monkeypatch):
    """Under the loop over the passes a recomputed block keeps the flash
    kernel's output and lse and nothing new (the cell has 0.45 GiB left
    and the loop's tuple holds every kept array twice): the step is the
    one compiled with the policy from before and with none of the new
    names, instruction for instruction."""
    import flax.linen as nn

    from horovod_tpu.models import transformer

    depth = SHALLOW["ouro_2_6b-spmd-1chip"]
    now = _instructions(cell_step("ouro_2_6b-spmd-1chip", depth=depth))
    flash = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    name = flash.checkpoint_name
    monkeypatch.setattr(flash, "checkpoint_name", lambda x, n: (
        name(x, n) if n in flash.SAVED_NAMES else x))
    monkeypatch.setattr(transformer, "checkpoint_name", lambda x, n: x)
    monkeypatch.setattr(transformer, "keeping", lambda block, names: nn.remat(
        block, policy=jax.checkpoint_policies.save_only_these_names(
            *flash.SAVED_NAMES)))
    before = _instructions(cell_step("ouro_2_6b-spmd-1chip",
                                     variant="policy-before", depth=depth))
    assert len(now) > 2000 and now == before


def test_a_cell_is_compiled_at_its_own_depth_once(cell_step):
    """What the tests above compiled (this one runs after them): a cell
    at its published depth once, under no other ``Transformer`` and no
    variant, but for OLMoE's, whose cell is one layer as it is (and
    Phi-4's once more for a device that tells no limit)."""
    print("\n".join(map(str, cell_step.misses)))
    at_depth = [key for key in cell_step.misses if not key[3]]
    assert len(set(at_depth)) == len(at_depth)
    assert {key[:4] for key in at_depth if key[1] or key[2]} <= {
        ("olmoe_1b_7b-spmd-1chip", None, "unnamed", ())}
    assert [key[0] for key in at_depth if key[4] is None] == [
        "phi4_mini_flash-spmd-1chip"]


@pytest.mark.parametrize("chips,compression,hierarchical", [
    (1, "none", False), (4, "none", False), (4, "bf16", False),
    (4, "int8", False), (4, "none", True)],
    ids=["1-exact", "4-exact", "4-bf16", "4-int8", "4-hierarchical"])
def test_eager_allreduce_response_compiles_for_v5e(v5e, chips, compression,
                                                   hierarchical):
    """The eager plane's one program a response
    (``ops/xla_executor.py:_build_allreduce``), for a bucket of ResNet-50
    gradients handed in as they are: a convolution's weights, a
    batch-norm scale, the classifier's bias and a scalar."""
    import numpy as np

    from horovod_tpu.common.ops_enum import ReduceOp
    from horovod_tpu.ops.xla_executor import XlaExecutor

    executor = XlaExecutor(v5e[:chips],
                           hier_local_size=2 if hierarchical else None)
    assert (executor.hier_mesh is not None) == hierarchical
    shapes = ((3, 3, 512, 512), (512,), (1000,), ())
    program = executor._build_allreduce(
        shapes, np.dtype(np.float32), ReduceOp.AVERAGE, 1.0, 1.0,
        hierarchical, compression)
    args = [jax.ShapeDtypeStruct(
        (chips * (shape or (1,))[0],) + shape[1:], jnp.float32,
        sharding=executor._sharded) for shape in shapes]
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    assert ("all-reduce" in text or "all-to-all" in text) == (chips > 1)
    outs = compiled.output_shardings
    assert len(outs) == len(shapes)
    assert all(out.is_fully_replicated for out in outs)
