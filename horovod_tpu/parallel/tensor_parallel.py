"""Tensor parallelism: GSPMD sharding rules for transformer weights.

The reference framework is data-parallel only (SURVEY §2.7); this module
provides the TPU-native tensor-parallel layer.  Rather than Megatron-style
hand-written column/row-parallel linear layers with explicit all-reduces,
the TPU idiom is GSPMD: annotate the *weights* with ``PartitionSpec``s and
constrain key *activations*, then let XLA insert the collectives on ICI
("pick a mesh, annotate shardings, let XLA insert collectives").

The canonical 2-way split for a transformer block (both halves need one
psum per block, which XLA fuses into the matmuls):

- attention qkv projection: column-parallel → heads split over ``tp``
- attention out projection: row-parallel
- MLP up projection: column-parallel; MLP down projection: row-parallel
- embedding / lm_head: vocab split over ``tp``

:func:`transformer_sharding_rules` maps parameter-path regexes to specs;
:func:`shard_params` applies them to a pytree.  Works with the flax
transformer in ``horovod_tpu.models.transformer`` and any pytree whose
path names follow the same conventions.
"""

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.mesh import as_mesh


def transformer_sharding_rules(tp_axis="tp", fsdp_axis=None):
    """[(path_regex, PartitionSpec)] for GPT-style parameter trees.

    Matching is ``re.search`` over the ``/``-joined parameter path, first
    match wins.  ``fsdp_axis`` additionally shards the non-tp dimension of
    the big matrices (ZeRO-3 style) when given.
    """
    f = fsdp_axis
    return [
        # attention; fused qkv DenseGeneral kernel is [d, 3, heads, d_head]
        # — split the heads dim
        (r"attn.*qkv.*kernel", P(f, None, tp_axis, None)),
        (r"attn.*(query|key|value).*kernel", P(f, tp_axis)),
        (r"attn.*(out|proj_out|output).*kernel", P(tp_axis, f)),
        # mlp
        (r"mlp.*(up|fc1|wi|gate).*kernel", P(f, tp_axis)),
        (r"mlp.*(down|fc2|wo).*kernel", P(tp_axis, f)),
        # moe experts: [n_experts, d_in, d_out]
        (r"moe.*(wi|wg|up|gate).*kernel", P("ep", f, tp_axis)),
        (r"moe.*(wo|down).*kernel", P("ep", tp_axis, f)),
        (r"moe.*router.*kernel", P(f, None)),
        # embeddings / head: vocab-split; position table replicated
        (r"pos_embed", P()),
        (r"(embed|wte).*embedding", P(tp_axis, f)),
        (r"(lm_head|output_head).*kernel", P(f, tp_axis)),
        # biases & layernorms replicated
        (r".*", P()),
    ]


def _path_str(path):
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for_path(path, rules):
    for pat, spec in rules:
        if re.search(pat, path):
            return spec
    return P()


def _fit_spec(spec, ndim):
    """Trim/pad a spec to the array rank (drop trailing axes that don't
    exist, e.g. biases matched by a kernel rule)."""
    parts = tuple(spec) + (None,) * max(0, ndim - len(spec))
    return P(*parts[:ndim])


def params_shardings(params, mesh, rules=None):
    """Pytree of NamedShardings matching ``params`` via the rule table.

    ``mesh`` may be a ``jax`` Mesh or an ``hvd.grid(...)`` Grid
    (docs/groups.md) — the grid resolves to the device mesh with the
    same axis names and C-order rank layout, so its ``tp`` group and
    the ``tp`` sharding axis name the same devices."""
    mesh = as_mesh(mesh)
    if rules is None:
        rules = transformer_sharding_rules()
    mesh_axes = set(mesh.axis_names)

    def one(path, x):
        spec = spec_for_path(_path_str(path), rules)
        # ignore axes the mesh doesn't have (e.g. no ep axis configured)
        parts = tuple(a if (a is None or a in mesh_axes) else None
                      for a in _fit_spec(spec, x.ndim))
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(one, params)


def shard_params(params, mesh, rules=None):
    """Place a parameter pytree onto the mesh per the sharding rules."""
    return jax.device_put(params, params_shardings(params, mesh, rules))


def constrain(x, mesh, *spec):
    """Activation sharding constraint (a true no-op if the mesh lacks
    every requested axis — mapping absent axes to None would impose a
    full-replication constraint, overriding GSPMD's propagated sharding
    and forcing an all-gather of e.g. batch-sharded MoE activations).
    ``mesh`` may be a Mesh or a Grid, as everywhere in this module."""
    mesh = as_mesh(mesh)
    mesh_axes = set(mesh.axis_names)
    parts = tuple(a if (a is None or a in mesh_axes) else None for a in spec)
    if not any(p is not None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))
