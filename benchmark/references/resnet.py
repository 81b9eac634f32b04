"""Plain reference of the ``resnet`` family: the bottleneck residual
network of He et al. 2015 (arXiv:1512.03385, Table 1) in its v1.5 form,
the stride of a down-sampling block on its 3x3 convolution, as the
program's ``BottleneckBlock`` has it. Training-mode BatchNorm over the
whole batch it is given, float32, convolutions at ``highest``. It reads
the parameter tree the program's ``ResNet`` reads and shares no code
with it.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references import common


def _blocks(cfg):
    """(name, filters, stride, has_projection) of every block."""
    out, cin, n = [], cfg["num_filters"], 0
    for stage, count in enumerate(cfg["stage_sizes"]):
        f = cfg["num_filters"] * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out.append((f"BottleneckBlock_{n}", cin, f, stride,
                        cin != 4 * f or stride != 1))
            cin, n = 4 * f, n + 1
    return out


def init_params(cfg, key):
    """Weights from ``key``: kernels normal with variance 1/fan_in,
    biases 0, BatchNorm scales 1 but for the last of each block, which
    starts at ``residual_scale_init`` (0.1). The program's own
    initialiser sets that one to 0 (Goyal et al. 2017), which would
    leave most of the first gradient zero and the comparison blind; at
    1 a random 50-layer BatchNorm network amplifies a rounding error
    with depth until bfloat16, int8 and float8 all read alike against
    float32 (PERF.md, Findings of PR 23)."""
    keys = iter(jax.random.split(key, 4 * len(_blocks(cfg)) + 2))

    def conv(kh, cin, cout):
        return {"kernel": jax.random.normal(
            next(keys), (kh, kh, cin, cout), jnp.float32)
            / math.sqrt(kh * kh * cin)}

    def bn(c, scale=1.0):
        return {"scale": jnp.full((c,), scale), "bias": jnp.zeros((c,))}

    width = cfg["num_filters"]
    params = {"conv_init": conv(7, cfg["channels"], width),
              "bn_init": bn(width)}
    for name, cin, f, _, proj in _blocks(cfg):
        block = {"Conv_0": conv(1, cin, f), "BatchNorm_0": bn(f),
                 "Conv_1": conv(3, f, f), "BatchNorm_1": bn(f),
                 "Conv_2": conv(1, f, 4 * f),
                 "BatchNorm_2": bn(4 * f, cfg["residual_scale_init"])}
        if proj:
            block["conv_proj"] = conv(1, cin, 4 * f)
            block["norm_proj"] = bn(4 * f)
        params[name] = block
    features = 4 * width * 2 ** (len(cfg["stage_sizes"]) - 1)
    params["Dense_0"] = {
        "kernel": jax.random.normal(
            next(keys), (features, cfg["num_classes"]), jnp.float32)
        / math.sqrt(features),
        "bias": jnp.zeros((cfg["num_classes"],))}
    return params


def init_aux(cfg):
    """Running statistics of every BatchNorm: mean 0, variance 1."""
    def stats(c):
        return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

    width = cfg["num_filters"]
    aux = {"bn_init": stats(width)}
    for name, _, f, _, proj in _blocks(cfg):
        aux[name] = {"BatchNorm_0": stats(f), "BatchNorm_1": stats(f),
                     "BatchNorm_2": stats(4 * f)}
        if proj:
            aux[name]["norm_proj"] = stats(4 * f)
    return {"batch_stats": aux}


def _conv(x, p, stride, padding, precision):
    def conv(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return common.product(conv, x, p["kernel"], precision)


def _batch_norm(x, p, running, cfg):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    m = cfg["batch_norm_momentum"]
    new = {"mean": m * running["mean"] + (1 - m) * mean,
           "var": m * running["var"] + (1 - m) * var}
    y = (x - mean) * lax.rsqrt(var + cfg["batch_norm_eps"])
    return y * p["scale"] + p["bias"], new


def _bottleneck(x, p, running, stride, cfg, precision):
    new = {}
    y = _conv(x, p["Conv_0"], 1, "SAME", precision)
    y, new["BatchNorm_0"] = _batch_norm(y, p["BatchNorm_0"],
                                        running["BatchNorm_0"], cfg)
    y = _conv(jax.nn.relu(y), p["Conv_1"], stride, "SAME", precision)
    y, new["BatchNorm_1"] = _batch_norm(y, p["BatchNorm_1"],
                                        running["BatchNorm_1"], cfg)
    y = _conv(jax.nn.relu(y), p["Conv_2"], 1, "SAME", precision)
    y, new["BatchNorm_2"] = _batch_norm(y, p["BatchNorm_2"],
                                        running["BatchNorm_2"], cfg)
    if "conv_proj" in p:
        x = _conv(x, p["conv_proj"], stride, "SAME", precision)
        x, new["norm_proj"] = _batch_norm(x, p["norm_proj"],
                                          running["norm_proj"], cfg)
    return jax.nn.relu(x + y), new


def logits_fn(params, aux, images, cfg, precision="float32"):
    running, new = aux["batch_stats"], {}
    x = images.astype(jnp.float32)
    x = _conv(x, params["conv_init"], 2, [(3, 3), (3, 3)], precision)
    x, new["bn_init"] = _batch_norm(x, params["bn_init"],
                                    running["bn_init"], cfg)
    x = lax.reduce_window(jax.nn.relu(x), -jnp.inf, lax.max,
                          (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for name, _, _, stride, _ in _blocks(cfg):
        block = jax.checkpoint(
            lambda x, p, r, stride=stride: _bottleneck(
                x, p, r, stride, cfg, precision))
        x, new[name] = block(x, params[name], running[name])
    x = jnp.mean(x, axis=(1, 2))
    kernel = params["Dense_0"]["kernel"]
    logits = common.einsum("bf,fc->bc", x, kernel, precision)
    return logits + params["Dense_0"]["bias"], {"batch_stats": new}


def loss_fn(params, aux, batch, cfg, precision="float32"):
    images, labels = batch
    logits, aux = logits_fn(params, aux, images, cfg, precision)
    return common.softmax_xent_mean(logits, labels), aux


# ---- what the mathematics requires, for ``mfu`` ---------------------------

def _conv_out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def layer_macs(cfg):
    """Every convolution and the dense layer, as (name, multiply-adds
    per image, needs_input_gradient), counted from shapes."""
    size = _conv_out(cfg["image_size"], 7, 2, 3)
    width = cfg["num_filters"]
    out = [("conv_init", size * size * 7 * 7 * cfg["channels"] * width,
            False)]
    size = _conv_out(size, 3, 2, 1)               # 3x3/2 max pool, SAME
    cin = width
    for stage, blocks in enumerate(cfg["stage_sizes"]):
        f = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            name = f"stage{stage}.block{block}"
            # v1.5: the stride sits on the 3x3, so the first 1x1 runs at
            # the input resolution.
            out.append((f"{name}.conv1x1a", size * size * cin * f, True))
            small = _conv_out(size, 3, stride, 1)
            out.append((f"{name}.conv3x3",
                        small * small * 9 * f * f, True))
            out.append((f"{name}.conv1x1b",
                        small * small * f * 4 * f, True))
            if cin != 4 * f or stride != 1:
                out.append((f"{name}.conv_proj",
                            small * small * cin * 4 * f, True))
            size, cin = small, 4 * f
    out.append(("dense", cin * cfg["num_classes"], True))
    return out


def flops_per_row(cfg, traffic):
    """Forward + backward FLOPs one image requires: 2 a multiply-add,
    three products a layer; the stem needs no gradient with respect to
    the image, so it counts 2 products. BatchNorm, ReLU, pooling and
    the optimizer count nothing."""
    return sum(2 * macs * (3 if needs_dx else 2)
               for _, macs, needs_dx in layer_macs(cfg))
