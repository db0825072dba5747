"""Pallas flash-attention kernel vs dense reference (interpret mode on CPU).

The kernel is the TPU hot-op (SURVEY §2.2: the reference has no compute
kernels of its own; this framework does).  Same test pattern as the rest:
random tensors, numpy-level expectation, gradients via autograd.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.ops.pallas.flash_attention import SAVED_NAMES
from horovod_tpu.parallel import reference_attention


def _rand(b=2, t=128, h=4, d=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _rand()
    expected = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    q, k, v = _rand(b=1, t=64, h=2, d=16, seed=1)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bf16():
    q, k, v = _rand(t=128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    expected = reference_attention(q, k, v, causal=True)
    got = flash_attention(qb, kb, vb, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(expected), rtol=0.1, atol=0.1)


def test_flash_non_pow2_seq():
    """Sequence length not divisible by 128: block picker shrinks blocks."""
    q, k, v = _rand(t=96, seed=2)
    expected = reference_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_shapes():
    """Tkv != Tq (cross attention, non-causal)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 64, 4, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 4, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 4, 32).astype(np.float32))
    expected = reference_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_in_transformer():
    """flash_attention drops into TransformerConfig.attn_fn."""
    from horovod_tpu.models import Transformer, TransformerConfig

    base = TransformerConfig(vocab_size=64, n_layers=1, d_model=32,
                             n_heads=2, d_ff=64, max_len=32,
                             dtype=jnp.float32)
    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=32,
                            n_heads=2, d_ff=64, max_len=32,
                            dtype=jnp.float32, attn_fn=flash_attention)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    params = Transformer(base).init(jax.random.PRNGKey(0), tokens)["params"]
    expected = Transformer(base).apply({"params": params}, tokens)
    got = Transformer(cfg).apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


def test_flash_in_ulysses():
    """flash_attention as the local kernel of Ulysses sequence parallelism."""
    from horovod_tpu.parallel import make_mesh, ulysses_self_attention

    mesh = make_mesh({"sp": 8})
    q, k, v = _rand(t=64, h=8, seed=4)
    expected = reference_attention(q, k, v, causal=True)
    got = ulysses_self_attention(q, k, v, mesh, causal=True,
                                 attn_fn=flash_attention)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- lse API
def test_flash_lse_matches_reference_logsumexp():
    q, k, v = _rand(b=1, t=64, h=2, d=16, seed=3)
    out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
    # dense logsumexp of the masked scores
    scale = 1.0 / np.sqrt(16)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) * scale
    msk = np.arange(64)[:, None] >= np.arange(64)[None, :]
    s = np.where(msk[None, None], s, -1e30)
    expect_lse = np.log(np.sum(np.exp(
        s - s.max(-1, keepdims=True)), -1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), expect_lse,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(reference_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)


def test_flash_lse_gradient():
    """The lse cotangent folds into delta (ds = p*(dp - delta + g_lse));
    check against autodiff through the dense logsumexp."""
    q, k, v = _rand(b=1, t=32, h=2, d=16, seed=4)
    scale = 1.0 / np.sqrt(16)

    def loss_flash(q, k, v):
        out, lse = flash_attention(q, k, v, causal=False, return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- flash inside ring
@pytest.mark.parametrize("causal", [False, True])
def test_flash_in_ring_attention(causal):
    """Ring attention with the Pallas kernel computing each local block
    (interpret mode on the 8-device CPU mesh) is exact attention."""
    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.ring_attention import ring_self_attention
    import functools
    from horovod_tpu.parallel._compat import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel.ring_attention import ring_attention

    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    b, t, h, d = 1, 64, 2, 16
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
               for _ in range(3))

    spec = P(None, "sp", None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp",
                          causal=causal, use_flash=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    out = fn(jax.device_put(q, sharding), jax.device_put(k, sharding),
             jax.device_put(v, sharding))
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_forward_statistics_are_lane_rows():
    """The forward kernel holds its scores transposed, ``[block_k,
    block_q]``: the running max and sum of a q block are carried as
    ``[1, block_q]`` lane rows and the output transposed, ``[d_v,
    block_q]``; nothing in the kernel is a ``[block_q, 1]`` column, and
    ``lse`` leaves as the row it is: no product moves it there (the
    kernel's only products are the two on its operands, a body)."""
    from horovod_tpu.ops.pallas.flash_attention import _fwd
    import functools as ft
    bh, t, d, block, block_k = 2, 256, 32, 128, 64
    q3 = jnp.zeros((bh, t, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(ft.partial(
        _fwd, scale=d ** -0.5, causal=True, block_q=block, block_k=block_k,
        interpret=True))(q3, q3, q3)
    (kernel,) = [eqn.params["jaxpr"] for eqn in jaxpr.jaxpr.eqns
                 if eqn.primitive.name == "pallas_call"]
    loops = [eqn for eqn in kernel.eqns
             if eqn.primitive.name in ("while", "scan")]
    assert len(loops) == 2  # whole blocks; blocks on the diagonal
    for loop in loops:
        carried = sorted(tuple(v.aval.shape) for v in loop.outvars)
        assert carried[-3:] == [(1, block), (1, block), (d, block)], carried
    shapes = {tuple(v.aval.shape) for eqn in _walk(kernel)
              for v in eqn.outvars if hasattr(v.aval, "shape")}
    assert (block, 1) not in shapes and (1, block) in shapes
    products = [eqn for eqn in _walk(kernel)
                if eqn.primitive.name == "dot_general"]
    assert len(products) == 2 * len(loops) == len(_operand_products(kernel))
    # the kernel's last act is to store that row
    assert kernel.eqns[-1].primitive.name == "swap"
    assert tuple(kernel.eqns[-1].invars[1].aval.shape) == (1, block)


def test_packed_lse_layout_engaged_and_dense():
    """VERDICT r2 item 6: with block_q=128 the backward's lse/delta ride
    a dense [bh, t/128, 1, 128] layout (128x less HBM than the broadcast
    fallback).  Check the forward's residual output shape directly and
    that long-T backward matches the dense reference."""
    from horovod_tpu.ops.pallas.flash_attention import _fwd
    rng = np.random.RandomState(11)
    bh, t, d = 2, 512, 32
    mk = lambda: jnp.asarray(rng.randn(bh, t, d).astype(np.float32))
    q3, k3, v3 = mk(), mk(), mk()
    out, lse = _fwd(q3, k3, v3, scale=d ** -0.5, causal=False,
                    block_q=128, block_k=128, interpret=True)
    assert lse.shape == (bh, t)

    # prove the PACKED layout is what the kernel writes to HBM: the
    # pallas_call's lse output aval must be [bh, t/128, 1, 128], not the
    # broadcast [bh, t, 128] (which would also reshape to (bh, t) after
    # the [:, :, 0] slice — shape of the public return can't catch it)
    import functools as ft
    jaxpr = jax.make_jaxpr(ft.partial(
        _fwd, scale=d ** -0.5, causal=False, block_q=128, block_k=128,
        interpret=True))(q3, k3, v3)
    pallas_out_shapes = [
        tuple(v.aval.shape)
        for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"
        for v in eqn.outvars]
    assert (bh, t // 128, 1, 128) in pallas_out_shapes, pallas_out_shapes
    assert (bh, t, 128) not in pallas_out_shapes, pallas_out_shapes

    # end-to-end gradient at t=512 (packed path active: block_q=128)
    q = jnp.asarray(rng.randn(1, 512, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 512, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 512, 2, 16).astype(np.float32))

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_long_sequence_backward_packed():
    """T=4096 causal backward through the packed lse/delta layout — the
    long-sequence regime the round-2 broadcast layout capped (its dkv
    kernel held full-T 128-lane tiles of both operands).  The backward
    kernel carries dq over eight k blocks a head here; all three
    gradients must be finite and non-trivial."""
    q, k, v = _rand(b=1, t=4096, h=1, seed=0)
    gq, gk, gv = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for name, g in (("dq", gq), ("dk", gk), ("dv", gv)):
        arr = np.asarray(g)
        assert np.isfinite(arr).all(), name
        assert np.abs(arr).max() > 0, name


def test_flash_block_env_overrides_validated(monkeypatch):
    """HVD_FLASH_BLOCK_Q/K override the defaults; non-positive or
    garbage values fall back instead of crashing _pick_block."""
    from horovod_tpu.ops.pallas.flash_attention import _env_block

    monkeypatch.setenv("HVD_FLASH_BLOCK_Q", "256")
    assert _env_block("HVD_FLASH_BLOCK_Q", 128) == 256
    for bad in ("0", "-128", "abc", ""):
        monkeypatch.setenv("HVD_FLASH_BLOCK_Q", bad)
        assert _env_block("HVD_FLASH_BLOCK_Q", 128) == 128

    # an explicit bad argument still fails loudly
    import pytest as _pytest

    from horovod_tpu.ops.pallas.flash_attention import _pick_block
    with _pytest.raises(ValueError, match="block size"):
        _pick_block(64, 0)


# ------------------------------------------------- bfloat16: operand dtype
# At heads of 64 with 128-blocks and T 384 a causal call has whole,
# diagonal and skipped block pairs.  Errors are taken like chip_smoke.py's
# ``rel_err``: the largest difference over the largest reference value.

BF16_T, BF16_D = 384, 64


def _bf16_case(seed=5):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.randn(1, BF16_T, 2, BF16_D).astype(np.float32)
    ).astype(jnp.bfloat16)
    return mk(), mk(), mk(), mk()  # q, k, v, dO


def _rounded_dense(q, k, v, do, causal, rounded):
    """Dense attention and its gradients under ``vdot(out, dO)``, written
    out by hand.  ``rounded=True`` rounds where the kernel does: operands
    of every product in the input dtype with float32 sums, ``p`` and
    ``ds`` rounded to it at their products, everything else float32.
    ``rounded=False`` is float32 throughout."""
    dt = q.dtype if rounded else jnp.float32
    cast = lambda x: x.astype(dt)
    hi = jax.lax.Precision.HIGHEST
    ein = lambda spec, a, b: jnp.einsum(
        spec, cast(a), cast(b), precision=hi,
        preferred_element_type=jnp.float32)
    scale = q.shape[-1] ** -0.5
    s = ein("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        msk = (jnp.arange(s.shape[-2])[:, None]
               >= jnp.arange(s.shape[-1])[None, :])
        s = jnp.where(msk[None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    out = cast(jnp.swapaxes(ein("bhqk,bkhd->bhqd", e, v) / l, 1, 2))
    p = jnp.exp(s - (m + jnp.log(l)))
    do32, out32 = do.astype(jnp.float32), out.astype(jnp.float32)
    delta = jnp.swapaxes(jnp.sum(do32 * out32, axis=-1), 1, 2)[..., None]
    dv = ein("bhqk,bqhd->bkhd", p, do)
    ds = p * (ein("bqhd,bkhd->bhqk", do, v) - delta) * scale
    dq = ein("bhqk,bkhd->bqhd", ds, k)
    dk = ein("bhqk,bqhd->bkhd", ds, q)
    return {"forward": [out], "gradients": [dq, dk, dv]}


def _rel_err(got, want):
    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("what", ["forward", "gradients"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_against_rounded_and_float32_reference(causal, what):
    from chip_smoke import KERNEL_TOL

    q, k, v, do = _bf16_case()

    def weighed(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128)
        return jnp.vdot(out.astype(jnp.float32),
                        do.astype(jnp.float32)), out

    (_, out), grads = jax.value_and_grad(
        weighed, (0, 1, 2), has_aux=True)(q, k, v)
    got = {"forward": [out], "gradients": list(grads)}[what]
    assert all(g.dtype == jnp.bfloat16 for g in got)
    rounded = _rounded_dense(q, k, v, do, causal, rounded=True)[what]
    exact = _rounded_dense(q, k, v, do, causal, rounded=False)[what]
    for g, r, e in zip(got, rounded, exact):
        # against the reference that rounds where the kernel does: what
        # is left is the order of the sums and the last bfloat16 bit
        assert _rel_err(g, r) <= 1e-2
        # against float32 throughout, at the chip smoke's tolerance
        assert _rel_err(g, e) <= KERNEL_TOL["bfloat16"] == 3e-2


# ----------------------------------- the mechanism engages: kernel jaxprs
def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _walk(jaxpr):
    """Every equation under ``jaxpr``, loops' and calls' bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _flash_kernel_jaxprs(dtype, causal):
    """``{kernel name: jaxpr}`` of the two kernels of one
    forward-and-backward call at heads of 64."""
    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32)
                           ).astype(dtype) for _ in range(3))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal).astype(jnp.float32)),
        (0, 1, 2)))(q, k, v)
    kernels = {}
    for eqn in _walk(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            kernel = eqn.params["jaxpr"]
            kernels[kernel.debug_info.func_name] = kernel
    assert sorted(kernels) == ["_bwd_kernel", "_fwd_kernel"], sorted(kernels)
    return kernels


def _operand_products(jaxpr):
    """The ``dot_general`` equations on q, k, v, dO, p and ds: all but
    the identity products that move a row of scalars into a column."""
    return [eqn for eqn in _walk(jaxpr)
            if eqn.primitive.name == "dot_general"
            and all(min(v.aval.shape) > 1 for v in eqn.invars)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_products_take_operands_in_the_input_dtype(dtype, causal):
    """bfloat16 inputs reach the MXU as bfloat16 (with ``p`` and ``ds``
    rounded to it) and float32 inputs as float32; every product
    accumulates in float32.  The backward kernel makes the five products
    of the mathematics a block pair (scores, ``dO v.T``, dv, dk, dq:
    none computed twice), and only dq's has a transposed left operand;
    of the forward's two, ``v.T @ p`` has (the scores are transposed, so
    the small operand is the one that is turned)."""
    per_body = {"_fwd_kernel": 2, "_bwd_kernel": 5}
    transposed = {"_fwd_kernel": 1, "_bwd_kernel": 1}
    exps = {"_fwd_kernel": 2, "_bwd_kernel": 1}  # p and alpha; p alone
    bodies = 2 if causal else 1  # whole blocks; blocks on the diagonal
    for name, jaxpr in _flash_kernel_jaxprs(dtype, causal).items():
        products = _operand_products(jaxpr)
        assert len(products) == per_body[name] * bodies, (name, products)
        lhs_contracts = []
        for eqn in products:
            assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
            assert eqn.outvars[0].aval.dtype == jnp.float32, eqn
            (lhs_contract, _), _ = eqn.params["dimension_numbers"]
            lhs_contracts.append(tuple(lhs_contract))
        assert lhs_contracts.count((0,)) == transposed[name] * bodies, name
        assert lhs_contracts.count((1,)) == (
            per_body[name] - transposed[name]) * bodies, name
        # one round of exponentials a block pair
        assert len([e for e in _walk(jaxpr) if e.primitive.name == "exp"
                    ]) == exps[name] * bodies, name


def test_flash_mask_work_only_on_the_diagonal():
    """A causal kernel runs two loops: one over the blocks every row sees
    whole, whose body has no iota, compare or select, and one over the
    blocks the diagonal crosses.  Without ``causal`` there is one loop
    and no mask work in it."""
    mask_work = {"iota", "select_n", "ge", "gt"}

    def loops(jaxpr):
        """The primitives in each loop body (a ``fori_loop`` is a
        ``while`` under traced bounds and a ``scan`` under static ones)."""
        body_of = {"while": "body_jaxpr", "scan": "jaxpr"}
        return [{e.primitive.name for e in _walk(
                    eqn.params[body_of[eqn.primitive.name]].jaxpr)}
                for eqn in jaxpr.eqns if eqn.primitive.name in body_of]

    causal = _flash_kernel_jaxprs(jnp.bfloat16, True)
    for name, jaxpr in causal.items():
        bodies = loops(jaxpr)
        assert len(bodies) == 2, (name, len(bodies))
        masked = [bool(body & mask_work) for body in bodies]
        assert sorted(masked) == [False, True], (name, bodies)
        assert all("dot_general" in body and "exp" in body
                   for body in bodies), name
    # the forward's masked tile pays ONE select over the tile, on the
    # scores: none on p and none on the rescale (no guard for a row that
    # has seen nothing), and its iotas are a row of query offsets and a
    # column of key offsets, none of the tile's size
    (masked_body,) = [
        list(_walk(eqn.params["body_jaxpr"].jaxpr))
        for eqn in causal["_fwd_kernel"].eqns if eqn.primitive.name == "while"
        and "select_n" in {e.primitive.name for e in _walk(
            eqn.params["body_jaxpr"].jaxpr)}]
    names = [e.primitive.name for e in masked_body]
    assert names.count("select_n") == 1 and names.count("ge") == 1
    assert sorted(tuple(e.outvars[0].aval.shape) for e in masked_body
                  if e.primitive.name == "iota") == [(1, 256), (256, 1)]
    for name, jaxpr in _flash_kernel_jaxprs(jnp.bfloat16, False).items():
        bodies = loops(jaxpr)
        assert len(bodies) == 1 and not bodies[0] & mask_work, (name, bodies)


# ------------------------------------------------------------- mask edges
def _grads_match_reference(q, k, v, causal, rtol=1e-4, **blocks):
    def loss(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v) ** 2)
        return jax.value_and_grad(f, (0, 1, 2))(q, k, v)

    want, want_g = loss(lambda q, k, v: reference_attention(
        q, k, v, causal=causal))
    got, got_g = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, **blocks))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=rtol)


@pytest.mark.parametrize("t_q,t_kv", [(256, 128), (128, 256), (384, 256)])
def test_flash_causal_cross_lengths(t_q, t_kv):
    """Causal with ``t_q != t_kv`` (the mask is aligned at the start, as
    ``reference_attention``'s): q blocks past the last K block see every
    block whole, K blocks past the last q block see nothing."""
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, t_q, 2, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(1, t_kv, 2, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(1, t_kv, 2, 32).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(reference_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)
    _grads_match_reference(q, k, v, True)


@pytest.mark.parametrize("blocks", [
    {},                                    # 192 -> one block of 192
    {"block_q": 64, "block_k": 96},        # diagonal crosses unevenly
    {"block_q": 96, "block_k": 32},
], ids=["default", "q64k96", "q96k32"])
def test_flash_causal_length_no_multiple_of_the_block(blocks):
    q, k, v = _rand(b=1, t=192, h=2, d=32, seed=9)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, **blocks)),
        np.asarray(reference_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)
    _grads_match_reference(q, k, v, True, **blocks)


@pytest.mark.parametrize("blocks", [
    {"block_q": 128, "block_k": 128},
    {"block_q": 256, "block_k": 128},      # two lane rows of lse a q block
    {"block_q": 128, "block_k": 256},
], ids=["q128k128", "q256k128", "q128k256"])
def test_flash_causal_lse_gradients_across_block_shapes(blocks):
    """``return_lse=True`` gradients, causal, against autodiff through the
    dense masked logsumexp, with whole and diagonal blocks."""
    q, k, v = _rand(b=1, t=512, h=1, d=32, seed=10)
    scale = 1.0 / np.sqrt(32)

    def loss_flash(q, k, v):
        out, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                                   **blocks)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        msk = jnp.arange(512)[:, None] >= jnp.arange(512)[None, :]
        s = jnp.where(msk[None, None], s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        out = reference_attention(q, k, v, causal=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------ default block sizes
@pytest.mark.parametrize("t,want,expected", [
    (1024, 512, 512),   # the swept shape
    (384, 512, 384),    # shorter than the default: one block
    (1280, 512, 256),   # 512 does not divide; the largest aligned one does
    (640, 512, 128),
    (96, 512, 96),      # no multiple of 128 divides: largest divisor
    (200, 128, 100),
])
def test_pick_block_prefers_lane_aligned_divisors(t, want, expected):
    from horovod_tpu.ops.pallas.flash_attention import _pick_block
    assert _pick_block(t, want) == expected


def test_flash_default_blocks_are_the_swept_ones(monkeypatch):
    """Without arguments or HVD_FLASH_BLOCK_Q/K a [*, 1024, *, 64] call
    runs 512 x 512 blocks (two q blocks a batch-head in the forward
    kernel's grid, two k blocks in the backward kernel's) and matches
    the dense reference through them."""
    monkeypatch.delenv("HVD_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("HVD_FLASH_BLOCK_K", raising=False)
    q, k, v = _rand(b=1, t=1024, h=1, d=64, seed=12)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True)),
        (0, 1, 2)))(q, k, v)
    grids = [eqn.params["grid_mapping"].grid for eqn in _walk(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert grids == [(1, 2)] * 2, grids
    _grads_match_reference(q, k, v, True)


def test_flash_layers_of_one_shape_share_one_traced_kernel():
    """A transformer calls the kernel once a layer with the same shapes;
    the calls share one traced (and so one lowered) kernel body instead
    of tracing it per layer, which is seconds of every job's set-up."""
    q, k, v = _rand(b=1, t=128, h=2, d=32, seed=13)

    def three_layers(q, k, v):
        for _ in range(3):
            q = flash_attention(q, k, v, causal=True)
        return q

    jaxpr = jax.make_jaxpr(three_layers)(q, k, v)
    holders = [eqn.params["jaxpr"] for eqn in _walk(jaxpr.jaxpr)
               if eqn.primitive.name == "jit" and any(
                   e.primitive.name == "pallas_call"
                   for e in eqn.params["jaxpr"].jaxpr.eqns)]
    assert len(holders) == 3, len(holders)
    assert len({id(h) for h in holders}) == 1


# ------------------------------------------- two widths: d_qk and d_v
def _rand_two_widths(d_qk, d_v, b=1, t=128, h=2, seed=3):
    rng = np.random.RandomState(seed)
    mk = lambda d: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return mk(d_qk), mk(d_qk), mk(d_v)


@pytest.mark.parametrize("d_qk,d_v", [(192, 128), (64, 64), (128, 128),
                                      (24, 16)])
def test_flash_two_widths_forward_and_all_three_gradients(d_qk, d_v):
    """q and k of the scores' width, v and the output of the values':
    latent attention's 192 / 128 beside the widths the other
    configurations run; the default scale is ``1 / sqrt(d_qk)``."""
    q, k, v = _rand_two_widths(d_qk, d_v)
    ct = jnp.asarray(np.random.RandomState(4).randn(
        *v.shape).astype(np.float32))
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    want = reference_attention(q, k, v, causal=True)
    assert got.shape == want.shape == q.shape[:-1] + (d_v,)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    explicit = flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=32, scale=1 / np.sqrt(d_qk))
    np.testing.assert_array_equal(got, explicit)

    def loss(attend, **blocks):
        return lambda q, k, v: jnp.vdot(
            attend(q, k, v, causal=True, **blocks), ct)

    gf = jax.grad(loss(flash_attention, block_q=64, block_k=32),
                  (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(reference_attention), (0, 1, 2))(q, k, v)
    for got, want, like in zip(gf, gr, (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_flash_two_widths_lse_and_its_gradient():
    """``return_lse`` at 192 / 128: the logsumexp of the scaled scores,
    differentiable, as ring attention combines partial results."""
    q, k, v = _rand_two_widths(192, 128, t=64)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(192)
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, -1))

    def weighed(attend):
        def loss(q, k, v):
            out, lse = attend(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
        return loss

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            return_lse=True, block_q=32,
                                            block_k=32)
    for got, want in zip(flash(q, k, v), dense(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.grad(weighed(flash), (0, 1, 2))(q, k, v),
                         jax.grad(weighed(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ------------------- the one backward kernel: dq carried over k blocks
def _dense_out_and_lse(q, k, v, causal, window=None):
    """Plain attention in float32 with its logsumexp ``[B, H, T]``, the
    mask aligned at the start as the kernel's is, k and v repeated to
    q's heads."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(u, q.shape[2] // k.shape[2], axis=2) for u in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    if causal:
        behind = (jnp.arange(q.shape[1])[:, None]
                  - jnp.arange(k.shape[1])[None, :])
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)
        s = jnp.where(seen, s, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                     precision="highest")
    return out, jax.nn.logsumexp(s, -1)


# ------------------------- the forward kernel at each kind of cell shape
def _forward_inputs(t, t_kv, heads, kv_heads, d_qk, d_v, dtype, seed=8):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape).astype(np.float32)
                             ).astype(dtype)
                 for shape in ((1, t, heads, d_qk), (1, t_kv, kv_heads, d_qk),
                               (1, t_kv, kv_heads, d_v)))


BF16, F32 = jnp.bfloat16, jnp.float32
# a small size of each kind of shape the benchmark's cells run the
# forward kernel at, blocks of 32: (t, t_kv, query heads, key-value
# heads, d_qk, d_v, causal, window, dtype)
FORWARD_CASES = {
    "heads-of-64": (128, 128, 2, 2, 64, 64, True, None, BF16),
    "heads-of-128": (128, 128, 2, 2, 128, 128, True, None, BF16),
    "192-over-128": (128, 128, 2, 2, 192, 128, True, None, BF16),
    "grouped-6-to-1": (128, 128, 6, 1, 128, 128, True, None, BF16),
    "grouped-9-to-1-window": (128, 128, 9, 1, 128, 128, True, 32, BF16),
    "grouped-4-to-1-heads-of-64": (128, 128, 8, 2, 64, 64, True, None, BF16),
    "window-equal-to-the-block": (128, 128, 2, 2, 64, 64, True, 32, F32),
    "window-smaller-than-the-block": (128, 128, 2, 2, 64, 64, True, 8, F32),
    "window-no-multiple-of-the-block": (128, 128, 2, 2, 64, 64, True, 40,
                                        F32),
    "keys-longer-than-queries": (64, 128, 2, 2, 64, 64, True, None, F32),
    "keys-shorter-than-queries": (128, 64, 2, 2, 64, 64, True, None, F32),
    "non-causal": (128, 128, 2, 2, 64, 64, False, None, BF16),
    "non-causal-keys-longer": (64, 128, 2, 2, 32, 32, False, None, F32),
    "float32-192-over-128": (128, 128, 2, 1, 192, 128, True, None, F32),
}


@pytest.mark.parametrize("case", FORWARD_CASES)
def test_flash_forward_out_and_lse_at_each_kind_of_cell_shape(case):
    """``out`` and ``lse`` of the forward kernel against plain attention
    in float32: heads of 64, of 128, 192 over 128, grouped key-value
    heads, a window equal to the block, smaller and no multiple of it,
    keys longer and shorter than the queries, no mask at all, float32."""
    t, t_kv, heads, kv_heads, d_qk, d_v, causal, window, dtype = \
        FORWARD_CASES[case]
    q, k, v = _forward_inputs(t, t_kv, heads, kv_heads, d_qk, d_v, dtype)
    out, lse = flash_attention(q, k, v, causal=causal, window=window,
                               block_q=32, block_k=32, return_lse=True)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    assert out.shape == (1, t, heads, d_v) and lse.shape == (1, heads, t)
    want_out, want_lse = _dense_out_and_lse(q, k, v, causal, window)
    # the kernel rounds p to the input dtype at its product
    tol = 2e-5 if dtype == F32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), want_out,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_q,block_k,window,unseen", [
    # the window's far edge: the block before the diagonal's comes first
    # and rows 7 to 15 of a q block see none of its keys
    (16, 16, 8, "rows that see nothing in a visited block"),
    # a q block of two k blocks and a window of 4: rows 19 to 31 see no
    # key of the first block they visit
    (32, 16, 4, "a row whose first visited tile is fully masked")],
    ids=["far-edge-block", "first-tile-fully-masked"])
def test_flash_forward_rows_that_see_nothing_in_a_tile(block_q, block_k,
                                                       window, unseen):
    """A row that sees no key of a tile it visits keeps its running max,
    sum and output through it (the masked scores are -inf and the max is
    never under the finite floor, so no guard on the tile is needed):
    ``lse`` is finite, ``out`` and the gradients through the backward
    kernel hold no NaN and are plain attention's."""
    from horovod_tpu.ops.pallas.flash_attention import _k_bounds
    t = 64
    blind = []  # q blocks with a row that sees no key of its first tile
    for iq in range(t // block_q):
        (first, _, masked), *_ = _k_bounds(
            iq, causal=True, block_q=block_q, block_k=block_k, t_kv=t,
            window=window)
        i = np.arange(iq * block_q, (iq + 1) * block_q)[:, None]
        j = np.arange(int(first) * block_k, (int(first) + 1) * block_k)
        sees = ((j <= i) & (i - j < window)).any(axis=1)
        assert masked
        blind += [] if sees.all() else [iq]
    assert blind, unseen
    q, k, v = _forward_inputs(t, t, 4, 2, 16, 16, F32)

    def loss(attn):
        def f(q, k, v):
            out, lse = attn(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(lse), (out, lse)
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)

    (_, (out, lse)), grads = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=block_q,
        block_k=block_k, return_lse=True))
    (_, (want_out, want_lse)), want = loss(
        lambda q, k, v: _dense_out_and_lse(q, k, v, True, window))
    assert np.isfinite(np.asarray(lse)).all()
    for got in (out, *grads):
        assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "t,t_kv,d_qk,d_v,blocks,causal,with_lse,dtype", [
        (256, 256, 192, 128, (128, 128), True, False, jnp.float32),
        (128, 256, 32, 32, (64, 64), True, False, jnp.float32),
        (384, 256, 32, 32, (128, 128), True, False, jnp.float32),
        (192, 192, 32, 32, (96, 64), True, False, jnp.float32),
        (192, 192, 24, 16, (32, 96), True, True, jnp.float32),
        (512, 512, 64, 64, (128, 128), True, False, jnp.float32),
        (512, 512, 64, 64, (256, 128), True, True, jnp.float32),
        (512, 512, 32, 32, (128, 256), False, True, jnp.float32),
        (256, 512, 32, 32, (128, 128), False, False, jnp.float32),
        (512, 512, 192, 128, (128, 128), True, False, jnp.bfloat16),
        (512, 512, 64, 64, (128, 128), True, True, jnp.bfloat16),
        (256, 256, 80, 80, (128, 64), False, False, jnp.bfloat16),
    ], ids=["widths-192-128", "t_kv-longer", "t_kv-shorter", "blocks-96-64",
            "blocks-32-96-lse", "four-k-blocks", "four-k-blocks-lse",
            "non-causal-lse", "non-causal-t_kv-longer", "bf16-192-128",
            "bf16-lse", "bf16-non-causal-scale-no-power-of-two"])
def test_flash_backward_one_kernel_against_plain_attention(
        t, t_kv, d_qk, d_v, blocks, causal, with_lse, dtype):
    """dq, dk and dv of the one backward kernel against ``jax.grad`` of
    plain attention in float32: dq is a head's sum over its k blocks,
    carried in the kernel from one grid step to the next, so the cases
    have up to four k blocks a head, two heads a batch entry (the carry
    starts again at each), ``t_kv != t``, blocks that 128 does not
    divide, both widths, a cotangent on ``lse`` (folded into ``delta``),
    no mask, and bfloat16 operands."""
    rng = np.random.RandomState(t + t_kv + d_qk)
    mk = lambda n, d: jnp.asarray(
        rng.randn(2, n, 2, d).astype(np.float32)).astype(dtype)
    q, k, v, ct = mk(t, d_qk), mk(t_kv, d_qk), mk(t_kv, d_v), mk(t, d_v)

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            total = jnp.vdot(out.astype(jnp.float32), ct.astype(jnp.float32))
            return total + (jnp.sum(jnp.sin(lse)) if with_lse else 0.0)
        return f

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, return_lse=True,
                               block_q=blocks[0], block_k=blocks[1])

    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _dense_out_and_lse(
        q, k, v, causal)), (0, 1, 2))(*(
            x.astype(jnp.float32) for x in (q, k, v)))
    for g, w, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
        else:  # the chip smoke's tolerance for bfloat16
            assert _rel_err(g, w) <= 3e-2


def test_flash_backward_states_the_vmem_it_needs():
    """The backward call asks for the scoped VMEM its own blocks take
    (q, dO and dq whole, the head's float32 dq, the tiles): more than
    the compiler's default at the latent-attention cell's shape, the
    default where that is enough, and never by a model's name."""
    from horovod_tpu.ops.pallas.flash_attention import (_VMEM_DEFAULT,
                                                       _bwd_vmem_bytes)

    small = _bwd_vmem_bytes(1024, 64, 64, 512, 512, 2)
    latent = _bwd_vmem_bytes(4096, 192, 128, 512, 512, 2)
    assert small == _VMEM_DEFAULT == 16 << 20
    # 192 lanes take 256: q and dq 2 MiB each and dO 1 MiB, twice; the
    # float32 sum 4 MiB; four float32 tiles 4 MiB
    assert 18 << 20 <= latent <= 48 << 20
    assert _bwd_vmem_bytes(4096, 192, 128, 512, 512, 4) > latent
    assert _bwd_vmem_bytes(8192, 192, 128, 512, 512, 2) > latent
    q = jnp.zeros((1, 4096, 1, 192), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), (0, 1, 2)))(
            q, q, q[..., :128])
    params = [eqn.params["compiler_params"]["mosaic_tpu"]
              for eqn in _walk(jaxpr.jaxpr)
              if eqn.primitive.name == "pallas_call"
              and eqn.params["jaxpr"].debug_info.func_name == "_bwd_kernel"]
    assert len(params) == 1
    assert params[0].vmem_limit_bytes == latent
    assert tuple(params[0].dimension_semantics) == ("parallel", "arbitrary")


# ------------------- under jax.checkpoint: the kernel's results are named
def _checkpointed_blocks(policy, return_lse, n=4):
    """``loss(q, k, v)`` through n ``jax.checkpoint``-ed layers of
    attention at 192 / 128 (with ``return_lse`` the lse is used too)."""
    def block(x, k, v):
        if return_lse:
            out, lse = flash_attention(x, k, v, causal=True,
                                       return_lse=True)
            out = out * jnp.tanh(lse).transpose(0, 2, 1)[..., None]
        else:
            out = flash_attention(x, k, v, causal=True)
        return x + jnp.pad(out, ((0, 0),) * 3 + ((0, 64),))

    block = jax.checkpoint(block, policy=policy)

    def loss(q, k, v):
        for _ in range(n):
            q = block(q, k, v)
        return jnp.sum(q ** 2)

    return loss


SAVES_THE_NAMES = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)


@pytest.mark.parametrize("return_lse", [False, True], ids=["out", "lse"])
def test_flash_under_a_checkpoint_that_saves_its_names_runs_once(return_lse):
    """Four checkpointed layers: the forward kernel four times and the
    backward kernels four times where the checkpoint's policy saves the
    two names; eight forward kernels under a plain checkpoint, which
    the names do not change."""
    q, k, v = _rand_two_widths(192, 128, t=64)

    def calls(policy):
        text = jax.jit(jax.value_and_grad(_checkpointed_blocks(
            policy, return_lse), (0, 1, 2))).lower(q, k, v).as_text()
        return (len(re.findall(r"call @_fwd(_\d+)?\(", text)),
                len(re.findall(r"call @_bwd(_\d+)?\(", text)))

    assert calls(None) == (8, 4)
    assert calls(SAVES_THE_NAMES) == (4, 4)
    # a policy that saves other names saves nothing of the kernel's
    assert calls(jax.checkpoint_policies.save_only_these_names(
        "something_else")) == (8, 4)


@pytest.mark.parametrize("return_lse", [False, True], ids=["out", "lse"])
def test_flash_saved_results_are_the_recomputed_ones_bit_for_bit(return_lse):
    q, k, v = _rand_two_widths(192, 128, t=64)
    got, want = (jax.jit(jax.value_and_grad(_checkpointed_blocks(
        policy, return_lse), (0, 1, 2)))(q, k, v)
        for policy in (SAVES_THE_NAMES, None))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a))) > 0
        np.testing.assert_array_equal(a, b)
