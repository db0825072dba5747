"""The chip's compiler, without the chip, for the cell
``smallthinker_21b_a3b-spmd-1chip`` (``tests/test_chip_compile.py`` says
what such a compile can and cannot show): its step at published widths
and its own depth compiled ONCE for a described TPU v5e.  A file of its
own so that the compile does not lengthen the one worker that carries
``tests/test_chip_compile.py`` (ROADMAP D19).  Nothing executes."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

CELL = "smallthinker_21b_a3b-spmd-1chip"
HBM_BYTES = 16 * 2 ** 30  # one v5e chip
V5E_BYTES_LIMIT = 16_911_433_728  # its ``memory_stats()["bytes_limit"]``
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


@pytest.fixture(scope="module")
def step(v5e):
    """``(compiled, plan)``: the cell's step as ``benchmark/run.py``
    builds it, compiled for one described chip whose memory the model
    is told (``device_memory_bytes`` stands at a v5e's limit, so
    ``kept_plan`` fills it as on the chip), on the TPU branch of the
    model and outside the persistent cache."""
    import optax
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.models import transformer as program
    from horovod_tpu.parallel import make_mesh

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
    try:
        from benchmark_toy import load_by_path
    finally:
        sys.path.pop(0)
    bench = load_by_path(os.path.join(REPO, "benchmark", "run.py"),
                         "hvd_benchmark_run_chip_compile_smallthinker")
    cell = bench.load_cell(REPO, CELL)
    mesh = make_mesh({"hvd": 1}, devices=v5e[:1])

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    opt, make = cell.loop.make_step(
        cell, optax.adamw(**cell.job["optimizer"]["args"]), mesh)
    params, extra = jax.eval_shape(
        lambda key: cell.family.init(cell.config, cell.job, key),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (cell.job["per_chip_batch"], cell.job["seq_len"]), jnp.int32)
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    patch.setattr(program, "device_memory_bytes",
                  lambda: (V5E_BYTES_LIMIT, None))
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = make.lower(
            shaped(params, P()), shaped(extra, P()),
            shaped(jax.eval_shape(opt.init, params), P()),
            shaped(tokens, P("hvd"))).compile()
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    plan = program.kept_plan(
        cell.family._program_config(cell.config),
        cell.job["per_chip_batch"], cell.job["seq_len"], V5E_BYTES_LIMIT)
    return compiled, plan


def _bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _scoped_vmem(call):
    """``(stated, used)`` bytes of scoped VMEM on a compiled Pallas
    custom call's line."""
    stated, used = (re.search(
        '"' + key + r'":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
        call) for key in ("scoped_memory_configs",
                          "used_scoped_memory_configs"))
    return stated and int(stated.group(1)), int(used.group(1))


def test_the_step_fits_one_chip_as_its_plan_says(step):
    """13.1 GiB compiled: under a chip's 16 GiB and the plan's line of
    15.0, over the 9.78 GiB of state alone; the plan (the largest of the
    step's moments, each gradient held from where it is made) predicts
    no less than the compiled step and keeps every layer's
    ``moe_gate`` and ``moe_up`` and block 0's ``moe_down`` within its
    budget (12.6 GiB and no ``moe_down`` while a whole gradient tree was
    charged at the backward's first block)."""
    from horovod_tpu.parallel import moe

    compiled, plan = step
    gib = 2 ** 30
    assert 13.0 * gib <= _bytes(compiled) <= 15.0 * gib < HBM_BYTES
    assert plan.params == 4 * 656_529_920
    assert plan.budget == int(0.95 * V5E_BYTES_LIMIT)
    assert plan.peak <= plan.budget and plan.moment == "block 3"
    assert plan.peak >= _bytes(compiled)
    assert plan.names == (moe.PRODUCT_NAMES,) + (
        (moe.PRODUCT_GATE, moe.PRODUCT_UP),) * 3


def test_the_flash_calls_take_grouped_heads_at_16384(step):
    """A flash forward and ONE backward kernel a block, none in the
    recomputation (output and lse are kept): q ``[28, 16384, 128]`` over
    k and v ``[4, 16384, 128]``, never repeated to q's heads; one global
    layer (no ``rope`` scope under it) and three window layers; the
    backward call states the VMEM its blocks take
    (``_bwd_vmem_bytes``: 73.1 MiB of the v5e's 128, a group of 7 at T
    16384) and Mosaic takes no more."""
    from horovod_tpu.ops.pallas.flash_attention import _bwd_vmem_bytes

    text = step[0].as_text()
    flash = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line
             and "/flash/" in line]
    wide, narrow = "bf16[28,16384,128]{2,1,0}", "bf16[4,16384,128]{2,1,0}"
    seen = []
    for line in flash:
        operands = line.split("operand_layout_constraints={", 1)[1]
        q, k, v = re.findall(r"bf16\[[\d,]+\]\{2,1,0\}", operands)[:3]
        assert (q, k, v) == (wide, narrow, narrow), operands[:200]
        assert "rematted_computation" not in line
        backward = "jit(_bwd)" in line
        seen.append(("window" if "/attn/window/" in line else "global",
                     "bwd" if backward else "fwd"))
        stated, used = _scoped_vmem(line)
        assert used <= stated <= 128 << 20
        if backward:
            assert stated == _bwd_vmem_bytes(16384, 128, 128, 512, 512, 2,
                                             7) == 76_677_120
    assert sorted(seen) == sorted(
        [("global", way) for way in ("fwd", "bwd")]
        + [("window", way) for way in ("fwd", "bwd")] * 3)
    assert not [line for line in text.splitlines()
                if " broadcast(" in line and re.search(
                    r"= bf16\[(1,)?28,16384,128\]", line)]
    assert "/attn/window/rope/" in text
    assert "/attn/global/rope/" not in text


def test_the_decision_is_made_ahead_and_not_again(step):
    """Under ``route_ahead``, directly under the block: the router's
    float32 product forward, again in the recomputation and its two
    gradients; ``top_k`` over ``[16384, 64]`` once a layer, forward
    only; no scope ``moe/route``; the held experts' grouped products
    forward, recomputed (the down product alone: gate and up are kept)
    and backward."""
    text = step[0].as_text()
    ahead = [line for line in text.splitlines() if "/route_ahead/" in line]
    assert ahead and "/moe/route/" not in text
    assert all(re.search(r"/block_\d/route_ahead/", line) for line in ahead)
    again = [line for line in ahead if "rematted_computation" in line]
    assert again and not [line for line in again
                          if " sort(" in line or "top_k" in line.split(
                              "metadata=")[0]]
    sorts = [line for line in text.splitlines() if " sort(" in line
             and ("/route_ahead/" in line or "(argsort)" in line)]
    assert len(sorts) == 4 * 2
    assert not [line for line in sorts if "rematted_computation" in line]
    assert text.count("%ragged-dot-none") >= 4 * 9
