"""What is particular to ``olmoe_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to: it takes nothing
of the path under test, its choice of the k experts is top-k's, and
computed in bfloat16 (the nearest precision below the stated one) it
comes out as not correct."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np

import benchmark_toy
from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "olmoe_1b_7b-spmd-1chip"


def test_reference_uses_no_sort_no_top_k_and_no_grouped_product():
    with open(os.path.join(BENCH, "models", "olmoe_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _rms_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("sort(", "top_k", "ragged", "horovod_tpu", "pallas"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    assert re.search(r"jax\.lax\.scan\(\s*add_expert", code)


def test_the_references_choice_is_top_ks_ties_to_the_lower_index(bench,
                                                                toy_root):
    """The gate the reference builds by counting who beats whom is
    ``lax.top_k``'s choice, also where probabilities tie."""
    cell = bench.load_cell(toy_root, CELL)
    config, family = cell.config, cell.family
    d, e, k = (config["hidden_size"], config["num_experts"],
               config["num_experts_per_tok"])
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (48, d))
    router = jax.random.normal(key, (d, e))
    router = router.at[:, 3].set(router[:, 5])  # experts 3 and 5 tie
    w = {"ln2": {"scale": jnp.ones((d,))},
         "moe": {"router_kernel": router,
                 "wg_kernel": jnp.zeros((e, d, 4)),
                 "wi_kernel": jnp.zeros((e, d, 4)),
                 # the experts' output is the gate itself, in column e
                 "wo_kernel": jnp.zeros((e, 4, d))}}
    h = family._rms_norm(x, w["ln2"]["scale"], config["rms_norm_eps"])
    probs = jax.nn.softmax(h @ router, -1)
    _, chosen = jax.lax.top_k(probs, k)
    want = np.zeros((48, e), bool)
    want[np.arange(48)[:, None], np.asarray(chosen)] = True
    _, load_balancing, _ = family._experts(x, w, config, None)
    f = want.mean(0)
    np.testing.assert_allclose(
        load_balancing, e * np.sum(f * np.asarray(probs).mean(0)),
        rtol=1e-5)
    assert (want[:, 3] & ~want[:, 5]).any()  # a tie was broken, downwards
    assert not (want[:, 5] & ~want[:, 3]).any()


def test_check_fails_on_the_reference_computed_in_bfloat16(bench, toy_root):
    cell = bench.load_cell(toy_root, CELL)
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference="bfloat16")
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
