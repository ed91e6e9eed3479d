"""Neural-network layer ops (the reference's "full" property ops).

TPU-native redesign of the ops registered with MXNET_REGISTER_OP_PROPERTY
(SURVEY §2.5 — Activation, BatchNorm, Convolution, Pooling, FullyConnected,
Dropout, Embedding, Concat, SliceChannel, …). Each reference op had a
device-templated mshadow/cuDNN kernel pair; here forward is a single jax
function — XLA lowers matmuls/convs onto the MXU and fuses elementwise ops,
and jax.vjp over the traced graph replaces every hand-written Backward
(ref file:line citations per op below).

bfloat16 note: these functions are dtype-polymorphic; the training APIs
choose f32 or bf16, and op outputs follow the data operand's dtype.
FullyConnected requests f32 accumulation via ``preferred_element_type``;
convolutions run bf16-in/bf16-out (jax 0.9's conv transpose rejects a
widened cotangent) and rely on XLA:TPU's f32 MXU accumulators — on
non-TPU backends low-precision conv accumulation is backend-default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from .registry import Field, OpDef, register


def _pair(v, n=2):
    v = tuple(v) if isinstance(v, (tuple, list)) else (v,)
    if len(v) == 1:
        v = v * n
    return v


def _conv_dnums(nspatial):
    sp = "DHW"[-nspatial:]
    return ("NC" + sp, "OI" + sp, "NC" + sp)


# -- Activation (ref: src/operator/activation-inl.h) ---------------------------
_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": lambda x: jnp.log1p(jnp.exp(-jnp.abs(x))) + jnp.maximum(x, 0),
}


def _activation_fwd(params, inputs, aux, is_train, rng):
    return [_ACTS[params["act_type"]](inputs[0])], []


register(
    OpDef(
        "Activation",
        _activation_fwd,
        params={"act_type": Field("str", required=True, enum=list(_ACTS))},
    )
)


# -- LeakyReLU (ref: src/operator/leaky_relu-inl.h) ----------------------------
def _leaky_relu_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    at = params["act_type"]
    slope = params["slope"]
    if at == "leaky":
        out = jnp.where(x > 0, x, slope * x)
    elif at == "elu":
        out = jnp.where(x > 0, x, slope * (jnp.exp(x) - 1.0))
    elif at == "prelu":
        gamma = inputs[1].reshape((1, -1) + (1,) * (x.ndim - 2))
        out = jnp.where(x > 0, x, gamma * x)
    elif at == "rrelu":
        if is_train and rng is not None:
            s = jax.random.uniform(
                rng, x.shape, minval=params["lower_bound"], maxval=params["upper_bound"]
            ).astype(x.dtype)
        else:
            s = jnp.asarray(
                (params["lower_bound"] + params["upper_bound"]) / 2.0, x.dtype
            )
        out = jnp.where(x > 0, x, s * x)
    else:
        raise MXNetError("unknown LeakyReLU act_type %s" % at)
    return [out], []


def _leaky_relu_args(params):
    return ["data", "gamma"] if params.get("act_type") == "prelu" else ["data"]


def _leaky_relu_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("LeakyReLU: data shape unknown")
    s = in_shapes[0]
    if params.get("act_type") == "prelu":
        return [s, (s[1],)], [s], []
    return [s], [s], []


register(
    OpDef(
        "LeakyReLU",
        _leaky_relu_fwd,
        params={
            "act_type": Field("str", default="leaky", enum=["leaky", "elu", "prelu", "rrelu"]),
            "slope": Field("float", default=0.25),
            "lower_bound": Field("float", default=0.125),
            "upper_bound": Field("float", default=0.334),
        },
        arguments=_leaky_relu_args,
        infer_shape=_leaky_relu_shape,
        need_rng=True,
    )
)


# -- FullyConnected (ref: src/operator/fully_connected-inl.h:242) --------------
def _fc_fwd(params, inputs, aux, is_train, rng):
    data = inputs[0]
    w = inputs[1]
    x = data.reshape(data.shape[0], -1)
    out = jnp.dot(x, w.T, preferred_element_type=jnp.float32).astype(x.dtype)
    if not params["no_bias"]:
        out = out + inputs[2].astype(out.dtype)
    return [out], []


def _fc_args(params):
    return ["data", "weight"] if params.get("no_bias") else ["data", "weight", "bias"]


def _fc_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("FullyConnected: data shape unknown")
    n = in_shapes[0][0]
    flat = int(_np.prod(in_shapes[0][1:]))
    nh = params["num_hidden"]
    ins = [in_shapes[0], (nh, flat)] + ([] if params["no_bias"] else [(nh,)])
    return ins, [(n, nh)], []


register(
    OpDef(
        "FullyConnected",
        _fc_fwd,
        params={
            "num_hidden": Field("int", required=True),
            "no_bias": Field("bool", default=False),
        },
        arguments=_fc_args,
        infer_shape=_fc_shape,
    )
)


# -- Convolution (ref: src/operator/convolution-inl.h:489) ---------------------
def _conv_fwd(params, inputs, aux, is_train, rng):
    data, weight = inputs[0], inputs[1]
    # operands must share a dtype (lax.conv requirement); the op's contract
    # is that the output follows data's dtype (mixed-precision: bf16
    # activations with f32 master weights compute in bf16 on the MXU)
    if weight.dtype != data.dtype:
        weight = weight.astype(data.dtype)
    nsp = data.ndim - 2
    stride = _pair(params["stride"] or (1,) * nsp, nsp)
    pad = _pair(params["pad"] or (0,) * nsp, nsp)
    dilate = _pair(params["dilate"] or (1,) * nsp, nsp)
    out = jax.lax.conv_general_dilated(
        data,
        weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=_conv_dnums(nsp),
        feature_group_count=params["num_group"],
        # no preferred_element_type: jax 0.9 conv transpose can't mix an
        # f32 cotangent with bf16 operands; XLA:TPU accumulates bf16 convs
        # in the MXU's f32 accumulators regardless, so bf16-in/bf16-out is
        # the fast AND safe mixed-precision shape
    )
    if not params["no_bias"]:
        bias = inputs[2].astype(out.dtype).reshape((1, -1) + (1,) * nsp)
        out = out + bias
    return [out], []


def _conv_out_dim(d, p, k, dil, s):
    return (d + 2 * p - (dil * (k - 1) + 1)) // s + 1


def _conv_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("Convolution: data shape unknown")
    dshape = in_shapes[0]
    nsp = len(dshape) - 2
    k = _pair(params["kernel"], nsp)
    stride = _pair(params["stride"] or (1,) * nsp, nsp)
    pad = _pair(params["pad"] or (0,) * nsp, nsp)
    dilate = _pair(params["dilate"] or (1,) * nsp, nsp)
    nf, ng = params["num_filter"], params["num_group"]
    wshape = (nf, dshape[1] // ng) + k
    out_sp = tuple(
        _conv_out_dim(dshape[2 + i], pad[i], k[i], dilate[i], stride[i])
        for i in range(nsp)
    )
    oshape = (dshape[0], nf) + out_sp
    ins = [dshape, wshape] + ([] if params["no_bias"] else [(nf,)])
    return ins, [oshape], []


_CONV_PARAMS = {
    "kernel": Field("shape", required=True),
    "stride": Field("shape", default=None),
    "dilate": Field("shape", default=None),
    "pad": Field("shape", default=None),
    "num_filter": Field("int", required=True),
    "num_group": Field("int", default=1),
    "workspace": Field("int", default=1024),  # accepted & ignored (XLA plans memory)
    "no_bias": Field("bool", default=False),
    "cudnn_tune": Field("any", default=None),  # accepted & ignored on TPU
    "cudnn_off": Field("bool", default=False),
}

register(
    OpDef(
        "Convolution",
        _conv_fwd,
        params=dict(_CONV_PARAMS),
        arguments=_fc_args,
        infer_shape=_conv_shape,
    )
)


# -- Deconvolution (ref: src/operator/deconvolution-inl.h) ---------------------
def _deconv_pad_adj(params, in_sp):
    """Effective (pad, adj) per spatial dim. With target_shape set, pad
    and adj are deduced so the output hits the target exactly and the
    explicit pad/adj params are ignored (ref: deconvolution-inl.h:64-88
    InferPad)."""
    nsp = len(in_sp)
    k = _pair(params["kernel"], nsp)
    stride = _pair(params["stride"] or (1,) * nsp, nsp)
    target = params.get("target_shape") or ()
    if any(target):
        target = _pair(target, nsp)
        pad, adj = [], []
        for i in range(nsp):
            total = stride[i] * (in_sp[i] - 1) + k[i]
            if total < target[i]:
                raise MXNetError(
                    "Deconvolution: target_shape %s too big (max %d on "
                    "axis %d)" % (target, total, i))
            excess = total - target[i]
            adj.append(excess % 2)
            pad.append((excess + 1) // 2)
        return tuple(pad), tuple(adj)
    pad = _pair(params["pad"] or (0,) * nsp, nsp)
    adj = _pair(params.get("adj") or (0,) * nsp, nsp)
    for i in range(nsp):
        if adj[i] >= max(stride[i], 1) and adj[i] != 0:
            raise MXNetError("Deconvolution: adj must be < stride")
    return pad, adj


def _deconv_fwd(params, inputs, aux, is_train, rng):
    data, weight = inputs[0], inputs[1]
    if weight.dtype != data.dtype:
        weight = weight.astype(data.dtype)
    nsp = data.ndim - 2
    stride = _pair(params["stride"] or (1,) * nsp, nsp)
    pad, adj = _deconv_pad_adj(params, data.shape[2:])
    k = _pair(params["kernel"], nsp)
    # transposed conv = gradient of conv wrt input: lhs-dilate by stride,
    # pad by k-1-p (adj extends the high side only — extra output rows
    # at the bottom/right, ref InferPad), spatially-flipped kernel with
    # I/O swapped (weight layout (in_ch, num_filter/group, *k),
    # ref deconvolution-inl.h:119)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nsp)))
    out = jax.lax.conv_general_dilated(
        data,
        w,
        window_strides=(1,) * nsp,
        padding=[(k[i] - 1 - pad[i], k[i] - 1 - pad[i] + adj[i])
                 for i in range(nsp)],
        lhs_dilation=stride,
        dimension_numbers=("NC" + "DHW"[-nsp:], "IO" + "DHW"[-nsp:], "NC" + "DHW"[-nsp:]),
        feature_group_count=params["num_group"],
        # see Convolution: no preferred_element_type for jax-0.9 AD compat
    )
    if not params["no_bias"]:
        out = out + inputs[2].astype(out.dtype).reshape((1, -1) + (1,) * nsp)
    return [out], []


def _deconv_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("Deconvolution: data shape unknown")
    dshape = in_shapes[0]
    nsp = len(dshape) - 2
    k = _pair(params["kernel"], nsp)
    stride = _pair(params["stride"] or (1,) * nsp, nsp)
    pad, adj = _deconv_pad_adj(params, dshape[2:])
    nf, ng = params["num_filter"], params["num_group"]
    wshape = (dshape[1], nf // ng) + k
    out_sp = tuple(
        stride[i] * (dshape[2 + i] - 1) + k[i] - 2 * pad[i] + adj[i]
        for i in range(nsp)
    )
    oshape = (dshape[0], nf) + out_sp
    ins = [dshape, wshape] + ([] if params["no_bias"] else [(nf,)])
    return ins, [oshape], []


_DECONV_PARAMS = dict(_CONV_PARAMS)
_DECONV_PARAMS.update({
    "adj": Field("shape", default=None),
    "target_shape": Field("shape", default=None),
})

register(
    OpDef(
        "Deconvolution",
        _deconv_fwd,
        params=_DECONV_PARAMS,
        arguments=_fc_args,
        infer_shape=_deconv_shape,
    )
)


# -- Pooling (ref: src/operator/pooling-inl.h:325) -----------------------------
def _pool_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    nsp = x.ndim - 2
    if params["global_pool"]:
        k = x.shape[2:]
        stride = (1,) * nsp
        pad = (0,) * nsp
    else:
        k = _pair(params["kernel"], nsp)
        stride = _pair(params["stride"] or (1,) * nsp, nsp)
        pad = _pair(params["pad"] or (0,) * nsp, nsp)
    dims = (1, 1) + k
    strides = (1, 1) + stride
    # 'full' convention (ceil output dims, ref pooling-inl.h:218) needs extra
    # high-side padding so reduce_window's floor formula hits the ceil size
    hi_pad = list(pad)
    if not params["global_pool"] and params["pooling_convention"] == "full":
        for i in range(nsp):
            out_d = _pool_out_dim(x.shape[2 + i], pad[i], k[i], stride[i], "full")
            need = (out_d - 1) * stride[i] + k[i] - (x.shape[2 + i] + 2 * pad[i])
            hi_pad[i] = pad[i] + max(0, need)
    padding = ((0, 0), (0, 0)) + tuple((p, hp) for p, hp in zip(pad, hi_pad))
    pt = params["pool_type"]
    # init values must be Python scalars, not arrays, or reduce_window's
    # autodiff rule rejects the computation (verified: LeNet backward)
    if pt == "max":
        init = -_np.inf if jnp.issubdtype(x.dtype, jnp.floating) else _np.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides, padding)
    else:
        out = jax.lax.reduce_window(x, 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0,
                                    jax.lax.add, dims, strides, padding)
        if pt == "avg":
            # reference divides by full kernel area incl. padding
            # (ref: pooling-inl.h Forward: scale 1/(ksize_y*ksize_x))
            out = out / float(_np.prod(k))
    return [out], []


def _pool_out_dim(d, p, k, s, convention):
    if convention == "full":
        import math

        return 1 + int(math.ceil((d + 2 * p - k) / float(s)))
    return 1 + (d + 2 * p - k) // s


def _pool_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("Pooling: data shape unknown")
    dshape = in_shapes[0]
    nsp = len(dshape) - 2
    if params["global_pool"]:
        oshape = dshape[:2] + (1,) * nsp
        return [dshape], [oshape], []
    k = _pair(params["kernel"], nsp)
    stride = _pair(params["stride"] or (1,) * nsp, nsp)
    pad = _pair(params["pad"] or (0,) * nsp, nsp)
    out_sp = tuple(
        _pool_out_dim(dshape[2 + i], pad[i], k[i], stride[i], params["pooling_convention"])
        for i in range(nsp)
    )
    return [dshape], [dshape[:2] + out_sp], []


register(
    OpDef(
        "Pooling",
        _pool_fwd,
        params={
            "kernel": Field("shape", required=True),
            "pool_type": Field("str", required=True, enum=["max", "avg", "sum"]),
            "global_pool": Field("bool", default=False),
            "pooling_convention": Field("str", default="valid", enum=["valid", "full"]),
            "stride": Field("shape", default=None),
            "pad": Field("shape", default=None),
        },
        infer_shape=_pool_shape,
    )
)


# -- BatchNorm (ref: src/operator/batch_norm-inl.h:314) ------------------------
def _bn_norm_fwd_impl(x, gamma, beta, eps, axes, bshape):
    # E[x^2]-E[x]^2 instead of jnp.var's E[(x-E[x])^2]: the two-pass
    # form must finish the mean reduction before it can START the
    # variance pass (two full HBM reads of the activation, serialized);
    # sum and sum-of-squares reduce in ONE fused read. f32 accumulation
    # keeps the cancellation benign for activation-scale data (the
    # cuDNN BN fast path makes the same trade). Clamp: cancellation
    # can produce a small negative where true var ~ 0.
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes)
    sqmean = jnp.mean(jnp.square(x32), axis=axes)
    var = jnp.maximum(sqmean - jnp.square(mean), 0.0)
    # multiply by rsqrt (not divide by sqrt): XLA:TPU keeps the division
    # out of the fused elementwise loop this way
    inv = jax.lax.rsqrt(var.reshape(bshape) + eps)
    y32 = (x32 - mean.reshape(bshape)) * inv
    y = (y32 * gamma.reshape(bshape) + beta.reshape(bshape)).astype(x.dtype)
    return y, mean, var, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_train_norm(x, gamma, beta, eps, axes, bshape):
    """Training-mode batch normalization with a hand-written backward.

    Why not plain autodiff: the traced chain upcasts the activation to
    f32 and the vjp then keeps full-size f32 intermediates (x32, the
    centered product) as residuals — under the bf16 mixed-precision
    policy that doubles the HBM bytes the backward re-reads for every
    BatchNorm in the network (the named ResNet-50 roofline residual,
    docs/perf_analysis.md). This custom vjp pins the residuals to the
    activation in its OWN storage dtype (the very buffer the preceding
    conv already wrote — XLA aliases it, so BN stores nothing
    full-size) plus per-channel f32 stats, and recomputes x_hat
    blockwise in the backward fused into the reduction reads. The
    gradient formulas are the reference's BatchNormBackward
    (ref: src/operator/batch_norm-inl.h:220-260) in the standard
    two-reduction form.
    """
    return _bn_norm_fwd_impl(x, gamma, beta, eps, axes, bshape)[:3]


def _bn_train_norm_fwd(x, gamma, beta, eps, axes, bshape):
    y, mean, var, inv = _bn_norm_fwd_impl(x, gamma, beta, eps, axes, bshape)
    return (y, mean, var), (x, mean, inv, gamma)


def _bn_train_norm_bwd(eps, axes, bshape, res, cts):
    x, mean, inv, gamma = res
    dy, dmean_ct, dvar_ct = cts
    n = 1.0
    for a in axes:
        n *= x.shape[a]
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xc = x32 - mean.reshape(bshape)
    xhat = xc * inv
    # two reductions in one fused read of (x, dy)
    dbeta = jnp.sum(dy32, axis=axes)
    dgamma = jnp.sum(dy32 * xhat, axis=axes)
    g = gamma.reshape(bshape) * inv
    dx32 = g * (dy32 - (xhat * dgamma.reshape(bshape)
                        + dbeta.reshape(bshape)) / n)
    # cotangents of the mean/var outputs: zero in the training path (the
    # moving-average update stop_gradients them) but kept exact so the
    # op stays a correct primitive wherever stats are consumed
    # differentiably; d var/dx uses the one-pass identity 2(x-mean)/n
    dx32 = dx32 + (dmean_ct.reshape(bshape)
                   + 2.0 * xc * dvar_ct.reshape(bshape)) / n
    return dx32.astype(x.dtype), dgamma, dbeta


_bn_train_norm.defvjp(_bn_train_norm_fwd, _bn_train_norm_bwd)


def _bn_fwd(params, inputs, aux, is_train, rng):
    # statistics and normalization in f32 regardless of activation dtype —
    # bf16 batch stats are numerically unusable (SURVEY §7 "dtype care");
    # residuals stay in the activation's storage dtype (custom vjp above)
    data, gamma, beta = inputs
    moving_mean, moving_var = aux
    eps, momentum = params["eps"], params["momentum"]
    if params["fix_gamma"]:
        gamma = jnp.ones_like(jax.lax.stop_gradient(gamma))
    axes = (0,) + tuple(range(2, data.ndim))
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    if is_train and not params["use_global_stats"]:
        out, mean, var = _bn_train_norm(
            data, gamma.astype(jnp.float32), beta.astype(jnp.float32),
            eps, axes, bshape)
        new_mm = moving_mean * momentum + jax.lax.stop_gradient(mean) * (1 - momentum)
        new_mv = moving_var * momentum + jax.lax.stop_gradient(var) * (1 - momentum)
        return [out], [new_mm, new_mv]
    mean = jax.lax.stop_gradient(moving_mean).astype(jnp.float32)
    var = jax.lax.stop_gradient(moving_var).astype(jnp.float32)
    inv = jax.lax.rsqrt(var.reshape(bshape) + eps)
    out = (data.astype(jnp.float32) - mean.reshape(bshape)) * inv
    out = out * gamma.astype(jnp.float32).reshape(bshape) + beta.astype(jnp.float32).reshape(bshape)
    return [out.astype(data.dtype)], [moving_mean, moving_var]


def _bn_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("BatchNorm: data shape unknown")
    c = (in_shapes[0][1],)
    return [in_shapes[0], c, c], [in_shapes[0]], [c, c]


def _bn_init_aux(params, aux_shapes):
    return [_np.zeros(aux_shapes[0], _np.float32), _np.ones(aux_shapes[1], _np.float32)]


register(
    OpDef(
        "BatchNorm",
        _bn_fwd,
        params={
            "eps": Field("float", default=1e-3),
            "momentum": Field("float", default=0.9),
            "fix_gamma": Field("bool", default=True),
            "use_global_stats": Field("bool", default=False),
        },
        arguments=("data", "gamma", "beta"),
        aux=("moving_mean", "moving_var"),
        infer_shape=_bn_shape,
        init_aux=_bn_init_aux,
    )
)


# -- InstanceNorm (ref: src/operator/instance_norm-inl.h) ----------------------
def _in_fwd(params, inputs, aux, is_train, rng):
    data, gamma, beta = inputs
    eps = params["eps"]
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) / jnp.sqrt(var + eps)
    return [out * gamma.reshape(bshape) + beta.reshape(bshape)], []


register(
    OpDef(
        "InstanceNorm",
        _in_fwd,
        params={"eps": Field("float", default=1e-3)},
        arguments=("data", "gamma", "beta"),
        infer_shape=lambda p, s: (
            [s[0], (s[0][1],), (s[0][1],)],
            [s[0]],
            [],
        ),
    )
)


# -- L2Normalization (ref: src/operator/l2_normalization-inl.h) ----------------
def _l2norm_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    eps = params["eps"]
    mode = params["mode"]
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return [x / norm], []


register(
    OpDef(
        "L2Normalization",
        _l2norm_fwd,
        params={
            "eps": Field("float", default=1e-10),
            "mode": Field("str", default="instance", enum=["instance", "channel", "spatial"]),
        },
    )
)


# -- LRN (ref: src/operator/lrn-inl.h) -----------------------------------------
def _lrn_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    alpha, beta, knorm, nsize = (
        params["alpha"],
        params["beta"],
        params["knorm"],
        params["nsize"],
    )
    sq = jnp.square(x)
    half = nsize // 2
    pads = [(0, 0)] * x.ndim
    pads[1] = (half, half)
    sq = jnp.pad(sq, pads)
    win = [1] * x.ndim
    win[1] = nsize
    ssum = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, tuple(win), (1,) * x.ndim,
        [(0, 0)] * x.ndim,
    )
    return [x / jnp.power(knorm + alpha / nsize * ssum, beta)], []


register(
    OpDef(
        "LRN",
        _lrn_fwd,
        params={
            "alpha": Field("float", default=1e-4),
            "beta": Field("float", default=0.75),
            "knorm": Field("float", default=2.0),
            "nsize": Field("int", required=True),
        },
    )
)


# -- Dropout (ref: src/operator/dropout-inl.h) ---------------------------------
def _dropout_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    p = params["p"]
    if not is_train or p <= 0.0:
        return [x], []
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)], []


register(
    OpDef(
        "Dropout",
        _dropout_fwd,
        params={"p": Field("float", default=0.5)},
        need_rng=True,
    )
)


# -- Embedding (ref: src/operator/embedding-inl.h:224) -------------------------
def _embedding_fwd(params, inputs, aux, is_train, rng):
    data, weight = inputs
    idx = data.astype(jnp.int32)
    return [jnp.take(weight, idx, axis=0)], []


def _embedding_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("Embedding: data shape unknown")
    d, o = params["input_dim"], params["output_dim"]
    return [in_shapes[0], (d, o)], [tuple(in_shapes[0]) + (o,)], []


register(
    OpDef(
        "Embedding",
        _embedding_fwd,
        params={
            "input_dim": Field("int", required=True),
            "output_dim": Field("int", required=True),
        },
        arguments=("data", "weight"),
        infer_shape=_embedding_shape,
    )
)


# -- Reshape / Flatten (ref: src/operator/reshape-inl.h) -----------------------
def _target_shape(params, in_shape):
    shape = params.get("shape") or ()
    if not shape and params.get("target_shape"):
        # legacy target_shape: (0, d1, d2, ...) with 0 = batch passthrough
        tgt = list(params["target_shape"])
        if tgt and tgt[0] == 0:
            tgt[0] = in_shape[0]
        return tuple(tgt)
    src = list(in_shape)
    if params.get("reverse"):
        src = src[::-1]
        shape = tuple(reversed(shape))
    out = []
    src_i = 0
    neg = -1
    for s in shape:
        if s == 0:  # copy corresponding input dim
            out.append(src[src_i])
            src_i += 1
        elif s == -1:
            neg = len(out)
            out.append(-1)
            src_i += 1
        else:
            out.append(s)
            src_i += 1
    total = int(_np.prod(in_shape))
    if neg >= 0:
        known = int(_np.prod([d for d in out if d != -1])) or 1
        out[neg] = total // known
    if params.get("reverse"):
        out = out[::-1]
    return tuple(out)


def _reshape_fwd(params, inputs, aux, is_train, rng):
    return [inputs[0].reshape(_target_shape(params, inputs[0].shape))], []


register(
    OpDef(
        "Reshape",
        _reshape_fwd,
        params={
            "shape": Field("shape", default=()),
            "target_shape": Field("shape", default=()),
            "keep_highest": Field("bool", default=False),
            "reverse": Field("bool", default=False),
        },
        infer_shape=lambda p, s: ([s[0]], [_target_shape(p, s[0])], []),
    )
)


def _flatten_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    return [x.reshape(x.shape[0], -1)], []


register(
    OpDef(
        "Flatten",
        _flatten_fwd,
        infer_shape=lambda p, s: (
            [s[0]],
            [(s[0][0], int(_np.prod(s[0][1:])))],
            [],
        ),
    )
)


# -- Concat (ref: src/operator/concat-inl.h) -----------------------------------
def _concat_fwd(params, inputs, aux, is_train, rng):
    return [jnp.concatenate(list(inputs), axis=params["dim"])], []


def _concat_shape(params, in_shapes):
    known = [s for s in in_shapes if s is not None]
    if not known:
        raise MXNetError("Concat: no input shape known")
    dim = params["dim"]
    out = list(known[0])
    out[dim] = sum(s[dim] for s in known)
    if len(known) != len(in_shapes):
        raise MXNetError("Concat: all input shapes must be known")
    return list(in_shapes), [tuple(out)], []


register(
    OpDef(
        "Concat",
        _concat_fwd,
        params={
            "num_args": Field("int", required=True),
            "dim": Field("int", default=1),
        },
        key_var_num_args="num_args",
        infer_shape=_concat_shape,
    )
)


# -- ElementWiseSum (ref: src/operator/elementwise_sum-inl.h) ------------------
def _ewsum_fwd(params, inputs, aux, is_train, rng):
    out = inputs[0]
    for x in inputs[1:]:
        out = out + x
    return [out], []


register(
    OpDef(
        "ElementWiseSum",
        _ewsum_fwd,
        params={"num_args": Field("int", required=True)},
        key_var_num_args="num_args",
    )
)


# -- SliceChannel (ref: src/operator/slice_channel-inl.h) ----------------------
def _slice_channel_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    n = params["num_outputs"]
    axis = params["axis"]
    outs = jnp.split(x, n, axis=axis)
    if params["squeeze_axis"]:
        outs = [jnp.squeeze(o, axis=axis) for o in outs]
    return outs, []


def _slice_channel_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("SliceChannel: data shape unknown")
    n, axis = params["num_outputs"], params["axis"]
    s = list(in_shapes[0])
    if s[axis] % n != 0:
        raise MXNetError("SliceChannel: axis %d size %d not divisible by %d" % (axis, s[axis], n))
    s[axis] //= n
    if params["squeeze_axis"] and s[axis] == 1:
        s = s[:axis] + s[axis + 1:]
    return [in_shapes[0]], [tuple(s)] * n, []


register(
    OpDef(
        "SliceChannel",
        _slice_channel_fwd,
        params={
            "num_outputs": Field("int", required=True),
            "axis": Field("int", default=1),
            "squeeze_axis": Field("bool", default=False),
        },
        outputs=lambda p: ["output%d" % i for i in range(p.get("num_outputs") or 1)],
        infer_shape=_slice_channel_shape,
    )
)


# -- Cast (ref: src/operator/cast-inl.h) ---------------------------------------
def _cast_fwd(params, inputs, aux, is_train, rng):
    return [inputs[0].astype(jnp.dtype(params["dtype"]))], []


def _cast_type(params, in_types):
    t = _np.dtype(params["dtype"])
    return [in_types[0] or _np.dtype("float32")], [t], []


register(
    OpDef(
        "Cast",
        _cast_fwd,
        params={"dtype": Field("str", required=True)},
        infer_type=_cast_type,
    )
)


# -- BlockGrad (ref: src/operator/block_grad-inl.h) ----------------------------
def _blockgrad_fwd(params, inputs, aux, is_train, rng):
    return [jax.lax.stop_gradient(inputs[0])], []


# no_head_grad: a BlockGrad head never propagates a cotangent, so
# backward() must not demand an out_grad for it (lets metrics-only heads
# ride alongside loss heads, e.g. the rcnn example's sampled-label head)
register(OpDef("BlockGrad", _blockgrad_fwd, no_head_grad=True))


# -- SwapAxis (ref: src/operator/swapaxis-inl.h) -------------------------------
def _swapaxis_fwd(params, inputs, aux, is_train, rng):
    return [jnp.swapaxes(inputs[0], params["dim1"], params["dim2"])], []


def _swapaxis_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("SwapAxis: data shape unknown")
    s = list(in_shapes[0])
    d1, d2 = params["dim1"], params["dim2"]
    s[d1], s[d2] = s[d2], s[d1]
    return [in_shapes[0]], [tuple(s)], []


register(
    OpDef(
        "SwapAxis",
        _swapaxis_fwd,
        params={"dim1": Field("int", default=0), "dim2": Field("int", default=0)},
        infer_shape=_swapaxis_shape,
    )
)


# -- SoftmaxActivation (ref: src/operator/softmax_activation-inl.h) ------------
def _softmax_act_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    if params["mode"] == "channel":
        return [jax.nn.softmax(x, axis=1)], []
    n = x.shape[0]
    return [jax.nn.softmax(x.reshape(n, -1), axis=-1).reshape(x.shape)], []


register(
    OpDef(
        "SoftmaxActivation",
        _softmax_act_fwd,
        params={"mode": Field("str", default="instance", enum=["instance", "channel"])},
    )
)


# -- Pad (ref: src/operator/pad-inl.h) -----------------------------------------
def _pad_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    pw = params["pad_width"]
    pads = [(pw[2 * i], pw[2 * i + 1]) for i in range(x.ndim)]
    mode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[params["mode"]]
    if mode == "constant":
        return [jnp.pad(x, pads, constant_values=params["constant_value"])], []
    return [jnp.pad(x, pads, mode=mode)], []


def _pad_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("Pad: data shape unknown")
    pw = params["pad_width"]
    s = tuple(
        d + pw[2 * i] + pw[2 * i + 1] for i, d in enumerate(in_shapes[0])
    )
    return [in_shapes[0]], [s], []


register(
    OpDef(
        "Pad",
        _pad_fwd,
        params={
            "mode": Field("str", required=True, enum=["constant", "edge", "reflect"]),
            "pad_width": Field("shape", required=True),
            "constant_value": Field("float", default=0.0),
        },
        infer_shape=_pad_shape,
    )
)


# -- UpSampling (ref: src/operator/upsampling-inl.h) ---------------------------
def _upsampling_fwd(params, inputs, aux, is_train, rng):
    scale = params["scale"]
    st = params["sample_type"]
    outs = []
    data_inputs = inputs if st == "nearest" else inputs[:1]
    for x in data_inputs:
        if st == "nearest":
            up = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        else:  # bilinear via deconv weight (inputs[1]) — approximate with resize
            up = jax.image.resize(
                x, x.shape[:2] + (x.shape[2] * scale, x.shape[3] * scale), "bilinear"
            )
        outs.append(up)
    if len(outs) == 1:
        return [outs[0]], []
    if params["multi_input_mode"] == "sum":
        out = outs[0]
        for o in outs[1:]:
            out = out + o
        return [out], []
    return [jnp.concatenate(outs, axis=1)], []


def _upsampling_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("UpSampling: data shape unknown")
    scale = params["scale"]
    s0 = in_shapes[0]
    oh, ow = s0[2] * scale, s0[3] * scale
    if params["sample_type"] == "bilinear":
        k = 2 * scale - scale % 2
        ws = (s0[1], 1, k, k)
        return [s0, ws], [(s0[0], s0[1], oh, ow)], []
    c = sum((s[1] if s else s0[1]) for s in in_shapes)
    if params["multi_input_mode"] == "sum":
        c = s0[1]
    return list(in_shapes), [(s0[0], c, oh, ow)], []


def _upsampling_args(params):
    if params.get("sample_type") == "bilinear":
        return ["data", "weight"]
    n = params.get("num_args") or 1
    return ["arg%d" % i for i in range(n)] if n > 1 else ["data"]


register(
    OpDef(
        "UpSampling",
        _upsampling_fwd,
        params={
            "scale": Field("int", required=True),
            "num_filter": Field("int", default=0),
            "sample_type": Field("str", required=True, enum=["nearest", "bilinear"]),
            "multi_input_mode": Field("str", default="concat", enum=["concat", "sum"]),
            "num_args": Field("int", default=1),
            "workspace": Field("int", default=512),
        },
        arguments=_upsampling_args,
        infer_shape=_upsampling_shape,
    )
)


# -- Crop (ref: src/operator/crop-inl.h) ---------------------------------------
def _crop_fwd(params, inputs, aux, is_train, rng):
    x = inputs[0]
    if params["num_args"] == 2:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = params["h_w"]
    if params["center_crop"]:
        y0 = (x.shape[2] - th) // 2
        x0 = (x.shape[3] - tw) // 2
    else:
        y0, x0 = params["offset"]
    return [x[:, :, y0:y0 + th, x0:x0 + tw]], []


def _crop_shape(params, in_shapes):
    if in_shapes[0] is None:
        raise MXNetError("Crop: data shape unknown")
    s0 = in_shapes[0]
    if params["num_args"] == 2:
        if in_shapes[1] is None:
            raise MXNetError("Crop: crop_like shape unknown")
        th, tw = in_shapes[1][2], in_shapes[1][3]
    else:
        th, tw = params["h_w"]
    return list(in_shapes), [(s0[0], s0[1], th, tw)], []


def _crop_args(params):
    return ["data", "crop_like"] if params.get("num_args") == 2 else ["data"]


register(
    OpDef(
        "Crop",
        _crop_fwd,
        params={
            "num_args": Field("int", required=True),
            "offset": Field("shape", default=(0, 0)),
            "h_w": Field("shape", default=(0, 0)),
            "center_crop": Field("bool", default=False),
        },
        arguments=_crop_args,
        infer_shape=_crop_shape,
    )
)


# -- IdentityAttachKLSparseReg (ref: src/operator/identity_attach_KL_sparse_reg-inl.h)
def _kl_sparse_fwd(params, inputs, aux, is_train, rng):
    sparseness_target = params["sparseness_target"]
    penalty = params["penalty"]
    momentum = params["momentum"]

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, x

    def bwd(x, g):
        rho_hat = jnp.mean(jax.nn.sigmoid(x), axis=0)
        t = sparseness_target
        grad_kl = penalty * (-t / (rho_hat + 1e-8) + (1 - t) / (1 - rho_hat + 1e-8))
        return (g + grad_kl[None, :] * jax.nn.sigmoid(x) * (1 - jax.nn.sigmoid(x)),)

    f.defvjp(fwd, bwd)
    del momentum  # moving-average penalty not modeled; direct penalty applied
    return [f(inputs[0])], []


register(
    OpDef(
        "IdentityAttachKLSparseReg",
        _kl_sparse_fwd,
        params={
            "sparseness_target": Field("float", default=0.1),
            "penalty": Field("float", default=0.001),
            "momentum": Field("float", default=0.9),
        },
    )
)
