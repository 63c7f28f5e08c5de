"""Family ``resnet``: ResNet v1.5 image classification, the reference's
own synthetic benchmark job.

The program under test is ``horovod_tpu.models.resnet.ResNet50`` with
the step of ``horovod_tpu.models.train_steps``; this file sizes it from
a configuration file, names its parameter shapes, makes its batch, and
holds its plain float32 reference, which imports nothing of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops

SAMPLE = "images"
BN_EPS = 1e-5
BN_MOMENTUM = 0.9
DIMS = ("NHWC", "HWIO", "NHWC")


def sizes(config: dict, per_chip_batch: int) -> dict:
    return {"stages": list(config["stage_sizes"]),
            "filters": config["num_filters"],
            "classes": config["num_classes"],
            "image": config["image_size"],
            "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return 1


def flops_per_sample(sz: dict) -> float:
    if (sz["stages"], sz["filters"], sz["image"]) != ([3, 4, 6, 3], 64, 224):
        raise ValueError("the FLOP count kept here is ResNet-50's at "
                         "224x224 and no other's")
    return flops.resnet50_flops_per_image()


def _blocks(sz):
    """(name, input channels, filters, stride) of every bottleneck."""
    out, cin, n = [], sz["filters"], 0
    for i, count in enumerate(sz["stages"]):
        f = sz["filters"] * 2 ** i
        for j in range(count):
            out.append((f"BottleneckBlock_{n}", cin, f,
                        2 if i > 0 and j == 0 else 1))
            cin, n = 4 * f, n + 1
    return out, cin


def param_shapes(sz: dict):
    params = {"conv_init": {"kernel": (7, 7, 3, sz["filters"])},
              "bn_init": {"scale": (sz["filters"],),
                          "bias": (sz["filters"],)}}
    stats = {"bn_init": {"mean": (sz["filters"],),
                         "var": (sz["filters"],)}}
    fan = {"params/conv_init/kernel": 7 * 7 * 3}

    def bn(block, name, c):
        params[block][name] = {"scale": (c,), "bias": (c,)}
        stats[block][name] = {"mean": (c,), "var": (c,)}

    def conv(block, name, k, cin, cout):
        params[block][name] = {"kernel": (k, k, cin, cout)}
        fan[f"params/{block}/{name}/kernel"] = k * k * cin

    blocks, width = _blocks(sz)
    for name, cin, f, stride in blocks:
        params[name], stats[name] = {}, {}
        conv(name, "Conv_0", 1, cin, f), bn(name, "BatchNorm_0", f)
        conv(name, "Conv_1", 3, f, f), bn(name, "BatchNorm_1", f)
        conv(name, "Conv_2", 1, f, 4 * f), bn(name, "BatchNorm_2", 4 * f)
        if cin != 4 * f or stride != 1:
            conv(name, "conv_proj", 1, cin, 4 * f)
            bn(name, "norm_proj", 4 * f)
    params["head"] = {"kernel": (width, sz["classes"]),
                      "bias": (sz["classes"],)}
    fan["params/head/kernel"] = width
    return {"params": params, "aux": stats}, fan


def make_batch(sz: dict, rows: int):
    def batch(key):
        k_img, k_lab = jax.random.split(key)
        images = jax.random.normal(
            k_img, (rows, sz["image"], sz["image"], 3), jnp.bfloat16)
        labels = jax.random.randint(k_lab, (rows,), 0, sz["classes"],
                                    jnp.int32)
        return images, labels
    return batch


# -- the program ----------------------------------------------------------

def build_model(sz: dict, axis_name="data"):
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet
    return ResNet(stage_sizes=sz["stages"], block_cls=BottleneckBlock,
                  num_classes=sz["classes"], num_filters=sz["filters"],
                  dtype=jnp.bfloat16, axis_name=axis_name)


def program_shapes(model, sz: dict):
    tree = jax.eval_shape(
        lambda k: model.clone(axis_name=None).init(
            k, jnp.zeros((1, sz["image"], sz["image"], 3), jnp.bfloat16),
            train=True),
        jax.random.key(0))
    return {"params": tree["params"], "aux": tree["batch_stats"]}


def injit_step(model, tx, mesh):
    from horovod_tpu.models import train_steps
    if model.num_classes != train_steps.RESNET_CLASSES:
        raise ValueError("train_steps.resnet_train_step is written for "
                         f"{train_steps.RESNET_CLASSES} classes")
    return train_steps.resnet_train_step(model, tx, mesh)


def injit_args(state: dict, batch: tuple) -> tuple:
    return (state["params"], state["aux"], state["opt"], *batch)


def injit_unpack(out, state: dict):
    params, aux, opt, loss = out
    return {"params": params, "aux": aux, "opt": opt}, loss


def program_loss(model):
    """``(params, aux, images, labels) -> (loss, aux)``: the loss of
    ``train_steps.resnet_train_step`` with batch norm over this
    process's rows alone, as upstream's eager job has it."""
    local = model.clone(axis_name=None)

    def loss_fn(params, aux, images, labels):
        logits, updates = local.apply(
            {"params": params, "batch_stats": aux}, images, train=True,
            mutable=["batch_stats"])
        one_hot = jax.nn.one_hot(labels, local.num_classes)
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * one_hot, -1))
        return loss, updates["batch_stats"]
    return loss_fn


# -- the plain reference --------------------------------------------------

def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                        dimension_numbers=DIMS)


def _bn(x, p, s):
    """Training-mode batch norm: (normalised x, updated running stats).
    The biased variance serves both, as in flax."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x), (0, 1, 2)) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
           "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    return y, new


def reference_stages(sz: dict) -> dict:
    """The model in float32, as stages for ``check.StagedGradient``:
    the stem, one bottleneck a stage, and the head with the loss."""
    blocks, _ = _blocks(sz)

    def stem(p, s, images, labels):
        x = _conv(images.astype(jnp.float32), p["conv_init"]["kernel"], 2)
        x, new = _bn(x, p["bn_init"], s["bn_init"])
        x = jax.lax.reduce_window(
            jax.nn.relu(x), -jnp.inf, jax.lax.max, (1, 3, 3, 1),
            (1, 2, 2, 1), "SAME")
        return x, {"bn_init": new}

    def bottleneck(stride):
        def run(p, s, x):
            new = {}
            y = _conv(x, p["Conv_0"]["kernel"])
            y, new["BatchNorm_0"] = _bn(y, p["BatchNorm_0"], s["BatchNorm_0"])
            y = _conv(jax.nn.relu(y), p["Conv_1"]["kernel"], stride)
            y, new["BatchNorm_1"] = _bn(y, p["BatchNorm_1"], s["BatchNorm_1"])
            y = _conv(jax.nn.relu(y), p["Conv_2"]["kernel"])
            y, new["BatchNorm_2"] = _bn(y, p["BatchNorm_2"], s["BatchNorm_2"])
            if "conv_proj" in p:
                x = _conv(x, p["conv_proj"]["kernel"], stride)
                x, new["norm_proj"] = _bn(x, p["norm_proj"], s["norm_proj"])
            return jax.nn.relu(x + y), new
        return run

    by_stride = {1: bottleneck(1), 2: bottleneck(2)}

    def head_loss(p, x, images, labels):
        x = jnp.mean(x, (1, 2))
        logits = x @ p["head"]["kernel"] + p["head"]["bias"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    return {"first": (("conv_init", "bn_init"), stem),
            "blocks": [(name, by_stride[stride])
                       for name, _, _, stride in blocks],
            "last": (("head",), head_loss)}
