"""Fused Pallas softmax cross-entropy vs the XLA oracle (interpret mode
on CPU; compiled Pallas on TPU — see tests/test_chip_compile.py and
chip_smoke.py)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.ops.pallas import softmax_xent, softmax_xent_reference


def _data(shape, v, seed=0, scale=3.0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(*shape, v).astype(np.float32) * scale)
    labels = jnp.asarray(rng.randint(0, v, shape))
    return logits, labels


@pytest.mark.parametrize("shape,v", [((4, 16), 512), ((3, 7), 1000),
                                     ((24,), 4096)])
def test_forward_matches_oracle_and_optax(shape, v):
    logits, labels = _data(shape, v)
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    ox = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ox),
                               rtol=1e-5, atol=1e-5)


def test_backward_matches_oracle_multi_grid():
    # n=24 rows -> block 8, grid 3: exercises cross-step independence
    logits, labels = _data((3, 8), 1024, seed=1)

    gp = jax.grad(lambda x: jnp.mean(softmax_xent(x, labels, True)))(logits)
    gr = jax.grad(
        lambda x: jnp.mean(softmax_xent_reference(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_extreme_logits_stable():
    """Online logsumexp must not overflow for large-magnitude logits."""
    logits, labels = _data((16,), 512, seed=2, scale=200.0)
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_bf16_logits_fp32_loss():
    logits, labels = _data((4, 8), 512, seed=3)
    lb = logits.astype(jnp.bfloat16)
    out = softmax_xent(lb, labels, True)
    assert out.dtype == jnp.float32
    ref = softmax_xent_reference(lb, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    g = jax.grad(lambda x: jnp.mean(softmax_xent(x, labels, True)))(lb)
    assert g.dtype == jnp.bfloat16


def test_odd_row_count_pads_and_slices():
    logits, labels = _data((5,), 768, seed=4)  # 5 rows -> pad to 8
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    gp = jax.grad(lambda x: jnp.sum(softmax_xent(x, labels, True)))(logits)
    gr = jax.grad(
        lambda x: jnp.sum(softmax_xent_reference(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_large_vocab_fwd_bwd():
    """vocab=32768 (production LM scale) through the VMEM-chunked
    streaming path, forward + backward vs optax."""
    logits, labels = _data((16,), 32768, seed=9, scale=1.0)

    got = softmax_xent(logits, labels, True)
    ref = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    gf = jax.grad(lambda l: jnp.mean(softmax_xent(l, labels, True)))(
        logits)
    gr = jax.grad(lambda l: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(l, labels)))(
        logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-5, atol=1e-7)


# ------------------------------------------------- the tile and its tail
# The kernels walk [rows, V] in [block_n, cols] tiles; ``_pick_tile``
# chooses them from what fits VMEM, and a V that cols does not divide
# gets a last tile that is masked (forward) or clipped (backward).  The
# module is shadowed by the function of its name in the package.
sx = importlib.import_module("horovod_tpu.ops.pallas.softmax_xent")
TAILS = [(1000, 256), (431, 128)]  # V, forced column tile: 232 and 47 over


@pytest.fixture
def forced_tile(monkeypatch):
    def force(block_n, cols):
        monkeypatch.setattr(sx, "_pick_tile",
                            lambda n, v, itemsize: (block_n, cols))
    return force


def _check_forward_and_gradient(logits, labels, rtol=1e-5, atol=1e-5,
                                gatol=1e-6):
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)
    gp = jax.grad(lambda x: jnp.mean(softmax_xent(x, labels, True)))(logits)
    gr = jax.grad(
        lambda x: jnp.mean(softmax_xent_reference(x, labels)))(logits)
    assert gp.dtype == logits.dtype and gp.shape == logits.shape
    np.testing.assert_allclose(np.asarray(gp, np.float32),
                               np.asarray(gr, np.float32),
                               rtol=rtol, atol=gatol)


@pytest.mark.parametrize("block_n", [8, 16])
@pytest.mark.parametrize("v,cols", TAILS)
def test_tail_forward_and_gradient_under_a_forced_tile(forced_tile, v, cols,
                                                       block_n):
    forced_tile(block_n, cols)
    _check_forward_and_gradient(*_data((2, 16), v, seed=5))


@pytest.mark.parametrize("v", [50304, 50257])
def test_lm_vocabularies_under_the_rules_own_tile(v):
    """OLMoE's and GPT-2's vocabularies at 8 rows, tiled as the rule
    says: 50257 has a tail wherever the tile is not the whole row."""
    logits, labels = _data((8,), v, seed=6, scale=1.0)
    _check_forward_and_gradient(logits, labels, gatol=1e-7)


@pytest.mark.parametrize("where", ["last_full_tile", "tail", "last_column"])
@pytest.mark.parametrize("v,cols", TAILS)
def test_label_in_the_last_full_tile_the_tail_and_the_last_column(
        forced_tile, v, cols, where):
    forced_tile(8, cols)
    full = v // cols * cols
    column = {"last_full_tile": full - 1, "tail": full,
              "last_column": v - 1}[where]
    logits, _ = _data((8,), v, seed=7)
    _check_forward_and_gradient(logits, jnp.full((8,), column, jnp.int32))


@pytest.mark.parametrize("v,cols", TAILS)
def test_extreme_logits_stable_with_a_tail(forced_tile, v, cols):
    forced_tile(8, cols)
    logits, labels = _data((16,), v, seed=2, scale=200.0)
    _check_forward_and_gradient(logits, labels, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("v,cols", TAILS)
def test_bf16_logits_with_a_tail(forced_tile, v, cols):
    forced_tile(16, cols)
    logits, labels = _data((4, 8), v, seed=3)
    _check_forward_and_gradient(logits.astype(jnp.bfloat16), labels,
                                rtol=1e-2, atol=1e-3, gatol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_vocabulary_of_bytes_under_one_tile(dtype):
    """320 columns (bytes and specials), fewer than any column tile and
    no multiple of 128 lanes: a row fits whole, so the tile is exactly the
    row and nothing is masked; rows as a head of several outputs a
    position gives them, ``[B, T, outputs, V]``."""
    assert sx._pick_tile(8 * 16384, 320, 4) == (256, 320)
    assert sx._pick_tile(48, 320, 2) == (16, 320)
    logits, labels = _data((2, 8, 3), 320, seed=8)
    loose = dtype == jnp.bfloat16
    _check_forward_and_gradient(
        logits.astype(dtype), labels, **(dict(
            rtol=1e-2, atol=1e-3, gatol=1e-3) if loose else {}))


# The vocabularies of the models queued next (ROADMAP R2-R7; the
# catalog's file is not in the repo) and of the two LM configurations.
VOCABULARIES = [32000, 50257, 50304, 100352, 128256, 128815, 129280, 131072,
                151936, 154880, 163840, 200064, 201024, 262272]
MAX_COLUMN_TILES = 128  # grid steps along V a row block; was 1,187 at 151,936


@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "f32"])
@pytest.mark.parametrize("v", VOCABULARIES)
def test_tile_rule_is_a_pure_function_of_rows_vocabulary_and_itemsize(
        v, itemsize):
    for n in (8, 24, 4096, 8192, 16384):
        block_n, cols = sx._pick_tile(n, v, itemsize)
        assert (block_n, cols) == sx._pick_tile(n, v, itemsize)
        assert cols == v or (cols % 128 == 0 and 0 < cols < v)
        assert block_n % 8 == 0 and n % block_n == 0
        assert sx._live_bytes(block_n, cols, itemsize) <= sx._VMEM_BUDGET
        assert sx._VMEM_BUDGET <= sx._VMEM_LIMIT
        assert -(-v // cols) <= MAX_COLUMN_TILES
