"""Family ``resnet``: the program's bottleneck ResNet v1.5
(``horovod_tpu.models.resnet``) built from a configuration file, the
operations one image requires, and a plain float32 reference.

The reference is written from He et al. (arXiv:1512.03385, Table 1)
with the v1.5 change (the stride of a down-sampling block sits on its
3x3 convolution): ``lax.conv_general_dilated`` and ``jax.numpy`` only.
It reads the program's parameter tree (flax's automatic names) and
nothing else of the program.
"""

import jax
import jax.numpy as jnp
from jax import lax

SAMPLE_UNIT = "images"
# |system - reference| / |reference| on a loss; the system runs its
# convolutions in bfloat16 with float32 parameters and statistics.
# Measured on the v5e (PERF.md, PR 22): forward 2e-4 of the loss; the
# change of the loss over one SGD-momentum step agrees to 2%.
TOLERANCE = {"forward": 5e-3, "update": 0.10}
# images in the group the update check repeats: the batch statistics
# of a group repeated k times are those of the group
CHECK_GROUP = 8


def _model(config):
    from horovod_tpu.models import resnet

    return resnet.ResNet(
        stage_sizes=config["stage_sizes"],
        block_cls=resnet.BottleneckBlock,
        num_classes=config["num_classes"],
        num_filters=config["num_filters"],
        dtype=jnp.dtype(config["activation_dtype"]))


def sample_units(config, job):
    return 1


def init(config, job, key):
    size = job["image_size"]
    variables = _model(config).init(
        key, jnp.zeros((1, size, size, 3), jnp.float32), train=True)
    return variables["params"], variables["batch_stats"]


def make_batch(config, job, key, n):
    """``n`` float32 images of unit normal noise and uniform labels, as
    the upstream's synthetic benchmark feeds them."""
    size = job["image_size"]
    k_x, k_y = jax.random.split(key)
    return {"image": jax.random.normal(k_x, (n, size, size, 3), jnp.float32),
            "label": jax.random.randint(
                k_y, (n,), 0, config["num_classes"], jnp.int32)}


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def loss(config, params, extra, batch):
    """The program's training-mode loss: ``(loss, new batch_stats)``."""
    logits, updates = _model(config).apply(
        {"params": params, "batch_stats": extra}, batch["image"],
        train=True, mutable=["batch_stats"])
    return _xent(logits, batch["label"]), updates["batch_stats"]


def _conv_layers(config, job):
    """Every convolution as ``(out_side, kernel, c_in, c_out,
    needs_input_gradient)``, and the classifier's ``(c_in, c_out)``."""
    side = job["image_size"] // 2
    width = config["num_filters"]
    convs = [(side, 7, 3, width, False)]  # nobody needs d loss / d image
    side //= 2  # the 3x3 max-pool, stride 2
    c_in = width
    for stage, blocks in enumerate(config["stage_sizes"]):
        mid = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = side // stride
            convs += [(side, 1, c_in, mid, True),
                      (out, 3, mid, mid, True),  # v1.5: stride here
                      (out, 1, mid, 4 * mid, True)]
            if c_in != 4 * mid or stride != 1:
                convs.append((out, 1, c_in, 4 * mid, True))
            side, c_in = out, 4 * mid
    return convs, (c_in, config["num_classes"])


def required_flops_per_sample(config, job):
    """Floating-point operations one image requires, forward and
    backward, convolutions and the classifier only (batch norm, ReLU
    and pooling are below 1%).  A product costs its multiply-adds
    twice; backward repeats it for the weight's gradient and, except at
    the first convolution, for the input's."""
    convs, (c_in, classes) = _conv_layers(config, job)
    total = 3 * 2 * c_in * classes
    for side, k, cin, cout, needs_dx in convs:
        total += (3 if needs_dx else 2) * 2 * side * side * k * k * cin * cout
    return total


# ------------------------------------------------------------ reference
def _conv(x, kernel, stride):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps):
    """Training mode: the batch's own statistics."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def reference_loss(config, params, extra, batch, perturb=None):
    """Float32 training-mode forward pass and loss; ``(loss, extra)``.
    The loss does not depend on the running statistics, which are
    returned as given.  ``perturb`` names a term to get wrong on
    purpose (tests of the check only)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps = config["batch_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = batch["image"].astype(jnp.float32)
        x = _conv(x, p["conv_init"]["kernel"], 2)
        x = jax.nn.relu(_batch_norm(x, p["bn_init"], eps))
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
        index = 0
        for stage, blocks in enumerate(config["stage_sizes"]):
            for block in range(blocks):
                w = p[f"BottleneckBlock_{index}"]
                index += 1
                stride = 2 if stage > 0 and block == 0 else 1
                y = _conv(x, w["Conv_0"]["kernel"], 1)
                y = jax.nn.relu(_batch_norm(y, w["BatchNorm_0"], eps))
                y = _conv(y, w["Conv_1"]["kernel"], stride)
                y = jax.nn.relu(_batch_norm(y, w["BatchNorm_1"], eps))
                y = _conv(y, w["Conv_2"]["kernel"], 1)
                y = _batch_norm(y, w["BatchNorm_2"], eps)
                if "conv_proj" in w:
                    x = _conv(x, w["conv_proj"]["kernel"], stride)
                    x = _batch_norm(x, w["norm_proj"], eps)
                x = x + y if perturb == "relu" else jax.nn.relu(x + y)
        x = jnp.mean(x, (1, 2))
        logits = x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]
        return _xent(logits, batch["label"]), extra
