"""Plain reference for the ``resnet50`` configuration.

He et al., "Deep Residual Learning for Image Recognition" (arXiv:1512.03385),
Table 1, the 50-layer column: a stem, a 3x3/2 max pool, [3, 4, 6, 3]
bottleneck blocks of widths 64/128/256/512 (x4 out), global average pool and
a 1000-way classifier; batch normalization after every convolution, trained
with SGD with momentum on the mean cross-entropy. Straightforward
``jax.numpy``/``lax`` in float32 at ``highest`` matmul precision. It imports
nothing of ``mxnet_tpu`` and takes nothing the program has made: the weights
are drawn here from the seed, and the driver hands the same draw to the
program under the program's own parameter names.

Departures, which follow the program and are stated in the configuration's
file: the stride of a block's first unit sits on its 3x3 convolution (the
"v1.5" placement); the 7x7/2 stem is the space-to-depth form (pad 3, pack
2x2 pixels into 12 channels, a 4x4 valid convolution), whose 4x4x12 weights
are drawn directly, so its receptive field is 8x8 and not 7x7.

``quant``: ``None`` is the reference; ``"fp8"`` is the control, the nearest
precision below the bfloat16 the configuration computes in: every tensor
the configuration's file states as bfloat16 (the operands of every
convolution and of the classifier, and the activations written between
operations: convolution outputs, BatchNorm outputs, residual sums) is
scaled by tensor into float8_e4m3's range and rounded to its 3 mantissa
bits, with a straight-through gradient. Float32 stays where the
configuration states float32 (master weights, BatchNorm statistics,
optimizer state). ``"fp8_operands"`` is a second, narrower control that
``calibrate.py`` reads beside it (``EXTRA_CONTROLS``): fp8 on the operands
of the convolutions and of the classifier alone, float32 between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import lowprec
import traffic

BN_EPS = 2e-5
UNITS = (3, 4, 6, 3)
WIDTHS = (256, 512, 1024, 2048)
#: controls ``calibrate.py`` reads besides ``"fp8"``
EXTRA_CONTROLS = ("fp8_operands",)


def conv_table(config):
    """(name, out, in, kernel) of every convolution, in forward order."""
    units = tuple(config.get("units", UNITS))
    widths = tuple(config.get("widths", WIDTHS))
    convs = [("conv0", widths[0] // 4, 12, 4)]
    cin = widths[0] // 4
    for s, (n, f) in enumerate(zip(units, widths)):
        for u in range(1, n + 1):
            name = "stage%d_unit%d" % (s + 1, u)
            convs.append((name + "_branch2a", f // 4, cin, 1))
            convs.append((name + "_branch2b", f // 4, f // 4, 3))
            convs.append((name + "_branch2c", f, f // 4, 1))
            if u == 1:
                convs.append((name + "_branch1", f, cin, 1))
            cin = f
    return convs


def forward_macs(config, image):
    """Multiply-adds of the convolutions and the classifier, forward, one
    image, counted layer by layer from the table above. The stem counts as
    the paper's 7x7/2 over 3 channels (the algorithm; the space-to-depth
    form multiplies 45 padded noughts more per output). The paper's Table
    1 says 3.8e9 for the 50-layer column with the stride on the first 1x1;
    on the 3x3 ("v1.5", as run) it is 4.09e9."""
    units = tuple(config.get("units", UNITS))
    widths = tuple(config.get("widths", WIDTHS))
    size = image // 2                                 # the stem, /2
    macs = size * size * (widths[0] // 4) * 3 * 49
    size //= 2                                        # the 3x3/2 max pool
    cin = widths[0] // 4
    for stage, (n_units, f) in enumerate(zip(units, widths)):
        for unit in range(n_units):
            out = size // 2 if (unit == 0 and stage > 0) else size
            macs += size * size * cin * (f // 4)      # 1x1 at the input size
            macs += out * out * (f // 4) * (f // 4) * 9   # 3x3, stride here
            macs += out * out * (f // 4) * f          # 1x1 expand
            if unit == 0:
                macs += out * out * cin * f           # projection shortcut
            cin, size = f, out
    return macs + cin * int(config["num_classes"])


def train_flops(config, mix):
    """Model FLOPs of one training step of this configuration under the
    mix ``mix``: forward and backward, 3 x 2 x multiply-adds an image.
    What ``mfu.train`` divides by time and peak."""
    return 6 * forward_macs(config, int(mix["image"])) * int(mix["batch"])


def _quantisers(quant):
    """(for operands, for activations written between operations)"""
    if quant == "fp8_operands":
        return lowprec.quantiser("fp8"), lowprec.quantiser(None)
    q = lowprec.quantiser(quant)
    return q, q


def _draw(config, key):
    """The weights under the program's parameter names, float32."""
    convs = conv_table(config)
    classes = int(config["num_classes"])
    keys = jax.random.split(key, len(convs) + 1)
    params = {}
    for k, (name, cout, cin, ks) in zip(keys, convs):
        fan_in = cin * ks * ks
        params[name + "_conv_weight"] = jax.random.normal(
            k, (cout, cin, ks, ks), jnp.float32) * np.sqrt(2.0 / fan_in)
        params[name + "_bn_gamma"] = jnp.ones((cout,), jnp.float32)
        params[name + "_bn_beta"] = jnp.zeros((cout,), jnp.float32)
    width = tuple(config.get("widths", WIDTHS))[-1]
    params["fc1_weight"] = jax.random.normal(
        keys[-1], (classes, width), jnp.float32) * 0.01
    params["fc1_bias"] = jnp.zeros((classes,), jnp.float32)
    return params


def make_params(config, seed):
    """One jitted call: every weight from the seed, on the device."""
    return jax.jit(lambda k: _draw(config, k))(traffic.key_of(seed))


def make_aux(config):
    """Moving mean 0 and variance 1 of every batch normalization."""
    aux = {}
    for name, cout, _, _ in conv_table(config):
        aux[name + "_bn_moving_mean"] = np.zeros((cout,), np.float32)
        aux[name + "_bn_moving_var"] = np.ones((cout,), np.float32)
    return aux


def leaf_names(config):
    return sorted(jax.eval_shape(lambda k: _draw(config, k),
                                 jax.random.PRNGKey(0)))


def leaf_norms(tree):
    """Norm of every leaf of a {name: array} dict, in sorted-name order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(tree[n].astype(jnp.float32))))
                      for n in sorted(tree)])


# -- the model -----------------------------------------------------------------


def _conv(x, w, stride, pad, q):
    q_op, q_act = q
    return q_act(lax.conv_general_dilated(
        q_op(x), q_op(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return ((x - mean) / jnp.sqrt(var + BN_EPS) * gamma[None, :, None, None]
            + beta[None, :, None, None])


def _conv_bn(p, name, x, stride, pad, q, relu=True):
    y = q[1](_bn(_conv(x, p[name + "_conv_weight"], stride, pad, q),
                 p[name + "_bn_gamma"], p[name + "_bn_beta"]))
    return jax.nn.relu(y) if relu else y


def logits_fn(p, images, config, quant=None):
    """images [N, 3, S, S] float32 -> logits [N, classes]."""
    q = q_op, q_act = _quantisers(quant)
    n, c, s, _ = images.shape
    h = (s + 6) // 2
    x = jnp.pad(images, ((0, 0), (0, 0), (3, 3), (3, 3)))
    x = x.reshape(n, c, h, 2, h, 2).transpose(0, 1, 3, 5, 2, 4)
    x = x.reshape(n, 4 * c, h, h)
    x = _conv_bn(p, "conv0", x, 1, 0, q)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for s_i, n_units in enumerate(tuple(config.get("units", UNITS))):
        for u in range(1, n_units + 1):
            name = "stage%d_unit%d" % (s_i + 1, u)
            stride = 2 if (u == 1 and s_i > 0) else 1

            @jax.checkpoint
            def unit(x, p=p, name=name, stride=stride, first=(u == 1)):
                y = _conv_bn(p, name + "_branch2a", x, 1, 0, q)
                y = _conv_bn(p, name + "_branch2b", y, stride, 1, q)
                y = _conv_bn(p, name + "_branch2c", y, 1, 0, q, relu=False)
                short = (_conv_bn(p, name + "_branch1", x, stride, 0, q,
                                  relu=False) if first else x)
                return jax.nn.relu(q_act(y + short))

            x = unit(x)
    x = jnp.mean(x, axis=(2, 3))
    return q_op(x) @ q_op(p["fc1_weight"]).T + p["fc1_bias"]


def loss_fn(p, images, labels, config, quant=None):
    logp = jax.nn.log_softmax(logits_fn(p, images, config, quant), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
    return -jnp.mean(picked)


def _decays(name):
    """The program's rule (the reference MXNet's): weight decay on
    ``*_weight`` and ``*_gamma``, none on biases and betas."""
    return name.endswith("_weight") or name.endswith("_gamma")


@functools.lru_cache(maxsize=None)
def _step_program(config_items, lr, momentum, wd, quant, keep_rows):
    config = dict(config_items)

    def step(p, mom, images, labels):
        if keep_rows is not None:  # the planted fault: rows left out
            images, labels = images[:keep_rows], labels[:keep_rows]
        loss, grad = jax.value_and_grad(loss_fn)(p, images, labels, config,
                                                 quant)
        new_mom = {n: momentum * mom[n] - lr * (
            grad[n] + (wd * p[n] if _decays(n) else 0.0)) for n in p}
        new_p = {n: p[n] + new_mom[n] for n in p}
        return new_p, new_mom, loss, leaf_norms(grad)

    return jax.jit(step, donate_argnums=(0, 1))


def _hashable(config):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in config.items()
                        if isinstance(v, (int, float, str, list))))


def train_readings(config, seed, images, labels, steps, lr, momentum, wd,
                   quant=None, keep_rows=None):
    """The numbers a training cell compares, from the reference: each
    step's loss, the first gradient's norm and the parameters' change after
    ``steps`` steps, leaf by leaf. ``images`` [P, B, 3, S, S] and ``labels``
    [P, B]: step t takes batch (t - 1) mod P, as the driver's pool serves."""
    with jax.default_matmul_precision("highest"):
        start = make_params(config, seed)
        p = jax.tree.map(jnp.copy, start)
        mom = jax.tree.map(jnp.zeros_like, p)
        prog = _step_program(_hashable(config), float(lr), float(momentum),
                             float(wd), quant, keep_rows)
        losses, first = [], None
        n_pool = images.shape[0]
        for t in range(steps):
            p, mom, loss, gnorm = prog(
                p, mom, jnp.asarray(images[t % n_pool], jnp.float32),
                jnp.asarray(labels[t % n_pool]))
            losses.append(loss)
            if first is None:
                first = gnorm
        change = jax.jit(lambda a, b: leaf_norms(
            {n: a[n] - b[n] for n in a}))(p, start)
    return dict(loss=np.asarray([float(v) for v in losses], np.float64),
                grad_norm=np.asarray(first, np.float64),
                change_norm=np.asarray(change, np.float64))
