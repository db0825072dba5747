"""The chip's compiler, without the chip, for the cell
``nemotron3_nano_30b_a3b-spmd-1chip`` (``tests/test_chip_compile.py`` says
what such a compile can and cannot show): its step at published widths
and its own depth compiled ONCE for a described TPU v5e.  A file of its
own so that the compile does not lengthen the one worker that carries
``tests/test_chip_compile.py`` (ROADMAP D19), as
``tests/test_chip_compile_smallthinker.py`` is.  Nothing executes."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

CELL = "nemotron3_nano_30b_a3b-spmd-1chip"
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
                         "hvd_benchmark_run_chip_compile_nemotron")
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


def test_the_step_fits_one_chip_and_its_plan_fills_it(step):
    """14.8 GiB compiled: under a chip's 15.75 GiB limit and the plan's
    line of 15.0, over the 9.94 GiB of state alone.  The plan keeps the
    first product of all four Mamba-2 layers, the shared expert's ``up``
    in all four expert layers and the held experts' ``up`` in three;
    its predicted peak (14.74 GiB at block 8, the last expert layer,
    where the backward pass starts) is within 0.15 GiB of the compiled
    step: the scan's masks and chunk states, made again inside a Mamba-2
    layer's backward pass, are a transient the plan does not count, and
    the gradients it holds to the end, which this step's fused AdamW
    consumes where they are made, stand against them."""
    from horovod_tpu.models.transformer import KEPT_IN, KEPT_UP
    from horovod_tpu.parallel import moe

    compiled, plan = step
    gib = 2 ** 30
    assert 14.5 * gib <= _bytes(compiled) <= 15.0 * gib < HBM_BYTES
    assert plan.params == 4 * 666_962_944
    assert plan.budget == int(0.95 * V5E_BYTES_LIMIT)
    assert plan.peak <= plan.budget and plan.moment == "block 8"
    assert abs(plan.peak - _bytes(compiled)) < 0.15 * gib
    experts = (KEPT_UP, moe.PRODUCT_UP)
    assert plan.names == ((KEPT_IN,), experts) * 2 + (
        (KEPT_IN,), (), experts, (KEPT_IN,), (KEPT_UP,))


def test_no_loop_in_the_mixers_and_the_scan_keeps_its_scopes(step):
    """The only ``while`` loops of the step are the held experts' passes
    over the rows that exist (``moe/dispatch``, ``moe/combine``): none
    under ``mixer/ssm``, forward, recomputed or backward (ROADMAP's
    lesson of PR 46), and every instruction of the scan carries
    ``mixer/ssm/scan`` with ``intra`` or ``inter`` inside it, the
    backward pass too."""
    text = step[0].as_text()
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 20
    assert all("/moe/moe/dispatch/" in line or "/moe/moe/combine/" in line
               for line in loops)
    scan = [line for line in text.splitlines() if "/mixer/ssm/scan/" in line]
    for phase, part in (("transpose(jvp(Transformer))", "intra"),
                        ("transpose(jvp(Transformer))", "inter"),
                        ("rematted_computation", "intra"),
                        ("jvp(Transformer)/block_0", "inter")):
        assert [line for line in scan if phase in line
                and f"/scan/checkpoint/{part}" in line.replace(
                    "rematted_computation/", "")], (phase, part)
    assert not [line for line in scan if "jvp(intra)" in line
                or "jvp(inter)" in line]
    # four layers' products by chunk: the masks [2,64,8,8,128,128]
    assert re.search(r"bf16\[2,64,8,8,128,128\]", text)


def test_the_flash_calls_take_two_key_value_heads_at_8192(step):
    """One flash forward and one backward kernel, none in the
    recomputation (output and lse are kept): q ``[64, 8192, 128]`` over
    k and v ``[4, 8192, 128]`` (2 sequences x 2 key-value heads, a group
    of 16), never repeated to q's heads; nothing is rotated."""
    text = step[0].as_text()
    flash = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line
             and "/flash/" in line]
    wide, narrow = "bf16[64,8192,128]{2,1,0}", "bf16[4,8192,128]{2,1,0}"
    seen = []
    for line in flash:
        operands = line.split("operand_layout_constraints={", 1)[1]
        q, k, v = re.findall(r"bf16\[[\d,]+\]\{2,1,0\}", operands)[:3]
        assert (q, k, v) == (wide, narrow, narrow), operands[:200]
        assert "rematted_computation" not in line
        assert "/attn/global/" in line
        seen.append("bwd" if "jit(_bwd)" in line else "fwd")
    assert sorted(seen) == ["bwd", "fwd"]
    assert "/rope/" not in text


def test_two_grouped_products_an_expert_layer_and_a_shared_expert(step):
    """The held experts' grouped products have no gate: ``moe_up`` and
    ``moe_down`` forward, and the shared expert runs under
    ``moe/shared`` in a block that has no mixer."""
    text = step[0].as_text()
    assert "/moe/moe/shared/" in text
    forward = [line for line in text.splitlines()
               if "ragged-dot" in line and " custom-call(" in line]
    assert forward
    assert not [line for line in text.splitlines() if "wg_kernel" in line]
