"""Layout-selection pass: rewrite NCHW conv subgraphs to NHWC with
transpose hoisting.

Every eligible ``Convolution`` is rewritten to compute channels-last,
and the transposes are HOISTED — a layout region grows forward through
every layout-capable consumer (BatchNorm, Pooling, Activation and all
plain elementwise ops), so ``conv -> bn -> relu -> conv`` chains carry NO
interior transposes; conversions happen only at region borders (the
data input, shortcut joins from NCHW producers, and graph heads /
layout-incapable consumers such as Flatten, whose element order depends
on the layout).

Weights stay in their reference OIHW layout (the bound parameter arrays,
checkpoints and the optimizer never see the rewrite); the NHWC conv op
transposes its weight operand inside the program, where XLA folds the
tiny permute into the conv's operand layout assignment.

Per-conv layout is a *contested* choice (small spatial dims or odd
channel counts can favor NCHW on some backends): with an autotuner the
decision is measured once on the real device and persisted in the
tuning DB keyed by (op, shapes, dtype, backend); without one, every
eligible conv converts (the measured default from the probe).

Numerics: convolution and BN reductions in NHWC sum in a different
order, so rewritten graphs are tolerance-equivalent, not bit-identical
(the golden-equivalence tests bound the drift; see
docs/how_to/compilation.md).
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from . import ir

__all__ = ["apply", "TO_NHWC", "TO_NCHW", "CONV_NHWC", "BN_NHWC",
           "POOL_NHWC"]

TO_NHWC = "_mxc_to_nhwc"
TO_NCHW = "_mxc_to_nchw"
CONV_NHWC = "_mxc_conv_nhwc"
BN_NHWC = "_mxc_bn_nhwc"
POOL_NHWC = "_mxc_pool_nhwc"


def _nchw_of(s):
    return (s[0], s[3], s[1], s[2])


def _nhwc_of(s):
    return (s[0], s[2], s[3], s[1])


# -- internal OpDefs (built lazily: this module only loads when the
#    pipeline runs, but keep jax imports inside forwards to match the
#    executor's import discipline) ---------------------------------------------
_OPS = {}


def _op(name):
    if not _OPS:
        _build_ops()
    return _OPS[name]


def _build_ops():
    from ..ops.registry import Field, OpDef
    from ..ops import nn as _nn

    def _t_nhwc_fwd(params, inputs, aux, is_train, rng):
        import jax.numpy as jnp

        return [jnp.transpose(inputs[0], (0, 2, 3, 1))], []

    def _t_nchw_fwd(params, inputs, aux, is_train, rng):
        import jax.numpy as jnp

        return [jnp.transpose(inputs[0], (0, 3, 1, 2))], []

    def _t_shape(perm_in, perm_out):
        def infer(params, in_shapes):
            s = in_shapes[0]
            if s is None:
                raise MXNetError("transpose: input shape unknown")
            if len(s) != 4:
                raise MXNetError("transpose: rank-4 input required")
            return [s], [perm_out(s)], []
        return infer

    _OPS[TO_NHWC] = OpDef(TO_NHWC, _t_nhwc_fwd,
                          infer_shape=_t_shape(_nchw_of, _nhwc_of),
                          doc="layout-pass NCHW->NHWC boundary transpose")
    _OPS[TO_NCHW] = OpDef(TO_NCHW, _t_nchw_fwd,
                          infer_shape=_t_shape(_nhwc_of, _nchw_of),
                          doc="layout-pass NHWC->NCHW boundary transpose")

    # -- NHWC convolution: data NHWC, weight kept OIHW --------------------------
    def _conv_nhwc_fwd(params, inputs, aux, is_train, rng):
        import jax
        import jax.numpy as jnp

        data, weight = inputs[0], inputs[1]
        if weight.dtype != data.dtype:
            weight = weight.astype(data.dtype)
        stride = _nn._pair(params["stride"] or (1, 1), 2)
        pad = _nn._pair(params["pad"] or (0, 0), 2)
        dilate = _nn._pair(params["dilate"] or (1, 1), 2)
        w = jnp.transpose(weight, (2, 3, 1, 0))  # OIHW -> HWIO
        out = jax.lax.conv_general_dilated(
            data, w,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=params["num_group"],
            # same accumulation contract as ops/nn.py _conv_fwd: no
            # preferred_element_type (jax conv transpose AD constraint);
            # XLA:TPU accumulates bf16 convs in f32 MXU accumulators
        )
        if not params["no_bias"]:
            out = out + inputs[2].astype(out.dtype).reshape((1, 1, 1, -1))
        return [out], []

    def _conv_nhwc_shape(params, in_shapes):
        if in_shapes[0] is None:
            raise MXNetError("conv_nhwc: data shape unknown")
        ins, outs, aux = _nn._conv_shape(
            params, [_nchw_of(in_shapes[0])] + list(in_shapes[1:]))
        return [_nhwc_of(ins[0])] + ins[1:], [_nhwc_of(outs[0])], aux

    from ..ops.nn import _CONV_PARAMS

    _OPS[CONV_NHWC] = OpDef(
        CONV_NHWC, _conv_nhwc_fwd, params=dict(_CONV_PARAMS),
        arguments=_nn._fc_args, infer_shape=_conv_nhwc_shape,
        doc="layout-pass channels-last Convolution (weights stay OIHW)")

    # -- NHWC BatchNorm: channel axis -1, same custom-vjp kernel ----------------
    def _bn_nhwc_fwd(params, inputs, aux, is_train, rng):
        import jax
        import jax.numpy as jnp

        data, gamma, beta = inputs
        moving_mean, moving_var = aux
        eps, momentum = params["eps"], params["momentum"]
        if params["fix_gamma"]:
            gamma = jnp.ones_like(jax.lax.stop_gradient(gamma))
        axes = (0, 1, 2)
        bshape = (1, 1, 1, -1)
        if is_train and not params["use_global_stats"]:
            out, mean, var = _nn._bn_train_norm(
                data, gamma.astype(jnp.float32), beta.astype(jnp.float32),
                eps, axes, bshape)
            new_mm = moving_mean * momentum + jax.lax.stop_gradient(mean) * (1 - momentum)
            new_mv = moving_var * momentum + jax.lax.stop_gradient(var) * (1 - momentum)
            return [out], [new_mm, new_mv]
        mean = jax.lax.stop_gradient(moving_mean).astype(jnp.float32)
        var = jax.lax.stop_gradient(moving_var).astype(jnp.float32)
        inv = jax.lax.rsqrt(var.reshape(bshape) + eps)
        out = (data.astype(jnp.float32) - mean.reshape(bshape)) * inv
        out = (out * gamma.astype(jnp.float32).reshape(bshape)
               + beta.astype(jnp.float32).reshape(bshape))
        return [out.astype(data.dtype)], [moving_mean, moving_var]

    def _bn_nhwc_shape(params, in_shapes):
        if in_shapes[0] is None:
            raise MXNetError("bn_nhwc: data shape unknown")
        c = (in_shapes[0][3],)
        return [in_shapes[0], c, c], [in_shapes[0]], [c, c]

    from ..ops.nn import _bn_init_aux

    _OPS[BN_NHWC] = OpDef(
        BN_NHWC, _bn_nhwc_fwd,
        params={
            "eps": Field("float", default=1e-3),
            "momentum": Field("float", default=0.9),
            "fix_gamma": Field("bool", default=True),
            "use_global_stats": Field("bool", default=False),
        },
        arguments=("data", "gamma", "beta"),
        aux=("moving_mean", "moving_var"),
        infer_shape=_bn_nhwc_shape,
        init_aux=_bn_init_aux,
        doc="layout-pass channels-last BatchNorm")

    # -- NHWC Pooling -----------------------------------------------------------
    def _pool_nhwc_fwd(params, inputs, aux, is_train, rng):
        import jax
        import jax.numpy as jnp

        x = inputs[0]
        if params["global_pool"]:
            k = x.shape[1:3]
            stride = (1, 1)
            pad = (0, 0)
        else:
            k = _nn._pair(params["kernel"], 2)
            stride = _nn._pair(params["stride"] or (1, 1), 2)
            pad = _nn._pair(params["pad"] or (0, 0), 2)
        dims = (1,) + k + (1,)
        strides = (1,) + stride + (1,)
        hi_pad = list(pad)
        if not params["global_pool"] and params["pooling_convention"] == "full":
            for i in range(2):
                out_d = _nn._pool_out_dim(
                    x.shape[1 + i], pad[i], k[i], stride[i], "full")
                need = (out_d - 1) * stride[i] + k[i] - (x.shape[1 + i] + 2 * pad[i])
                hi_pad[i] = pad[i] + max(0, need)
        padding = ((0, 0),) + tuple(
            (p, hp) for p, hp in zip(pad, hi_pad)) + ((0, 0),)
        pt = params["pool_type"]
        if pt == "max":
            init = (-_np.inf if jnp.issubdtype(x.dtype, jnp.floating)
                    else _np.iinfo(x.dtype).min)
            out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides,
                                        padding)
        else:
            out = jax.lax.reduce_window(
                x, 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0,
                jax.lax.add, dims, strides, padding)
            if pt == "avg":
                out = out / float(_np.prod(k))
        return [out], []

    def _pool_nhwc_shape(params, in_shapes):
        if in_shapes[0] is None:
            raise MXNetError("pool_nhwc: data shape unknown")
        ins, outs, aux = _nn._pool_shape(params, [_nchw_of(in_shapes[0])])
        return [_nhwc_of(ins[0])], [_nhwc_of(outs[0])], aux

    _OPS[POOL_NHWC] = OpDef(
        POOL_NHWC, _pool_nhwc_fwd,
        params={
            "kernel": Field("shape", required=True),
            "pool_type": Field("str", required=True,
                               enum=["max", "avg", "sum"]),
            "global_pool": Field("bool", default=False),
            "pooling_convention": Field("str", default="valid",
                                        enum=["valid", "full"]),
            "stride": Field("shape", default=None),
            "pad": Field("shape", default=None),
        },
        infer_shape=_pool_nhwc_shape,
        doc="layout-pass channels-last Pooling")


# -- capability + region growth ------------------------------------------------

def _capability(node, shapes):
    """How this node can participate in an NHWC region:
    'conv' (region seed), 'bn'/'pool' (converted in place),
    'eltwise' (layout-agnostic passthrough) or None (region border)."""
    if node.is_variable:
        return None
    out_shape = shapes.get((id(node), 0))
    if out_shape is None or len(out_shape) != 4:
        return None
    name = node.op.name
    if name == "Convolution":
        dshape = shapes.get((id(node.inputs[0][0]), node.inputs[0][1]))
        if dshape is not None and len(dshape) == 4:
            return "conv"
        return None
    if name == "BatchNorm":
        return "bn"
    if name == "Pooling":
        return "pool"
    if ir.is_elementwise(node):
        return "eltwise"
    return None


def apply(sym, input_shapes=None, input_types=None, tuner=None):
    """Rewrite eligible NCHW conv subgraphs to NHWC.

    Returns ``(new_sym, n_converted_convs)``; ``new_sym is sym`` when
    nothing converted. ``input_shapes`` seeds the shape sweep that
    gates eligibility (the executor passes its bound arg shapes)."""
    nodes = sym.nodes
    seed = {}
    for n in nodes:
        if not n.is_variable:
            continue
        s = None
        if input_shapes and n.name in input_shapes:
            s = tuple(input_shapes[n.name])
        else:
            raw = n.attrs.get("__shape__")
            if raw:
                import ast

                try:
                    s = tuple(int(d) for d in ast.literal_eval(str(raw)))
                except (ValueError, SyntaxError, TypeError):
                    s = None
        if s is not None:
            seed[(id(n), 0)] = s
    shapes = ir.propagate_shapes(nodes, seed) if seed else {}
    if not shapes:
        return sym, 0
    dtype_map = {}
    if tuner is not None and input_types:
        tseed = {(id(n), 0): _np.dtype(input_types[n.name])
                 for n in nodes
                 if n.is_variable and n.name in input_types}
        # tuning decisions key by the dtype each conv ACTUALLY computes
        # in — an interior edge for every layer past the first, so the
        # bound-argument dtypes must propagate through the graph
        dtype_map = ir.propagate_dtypes(nodes, tseed) if tseed else {}

    nhwc, n_convs = set(), 0
    for n in nodes:
        kind = _capability(n, shapes)
        if kind == "conv":
            if tuner is not None:
                dshape = shapes.get((id(n.inputs[0][0]), n.inputs[0][1]))
                dtype = dtype_map.get((id(n.inputs[0][0]), n.inputs[0][1]))
                choice = tuner.pick_conv_layout(n.params, dshape, dtype)
            else:
                choice = "nhwc"
            if choice == "nhwc":
                nhwc.add(id(n))
                n_convs += 1
        elif kind in ("bn", "pool", "eltwise"):
            if any(id(s) in nhwc for s, _ in n.inputs):
                nhwc.add(id(n))
    if not nhwc:
        return sym, 0

    from ..symbol import _Node, Symbol

    t_cache = {}  # (id(clone), oidx, target) -> transpose node

    def _wrap(entry, target):
        """Insert a boundary transpose around a cloned entry (cached so
        one conversion serves every consumer — the hoisting)."""
        node, oidx = entry
        key = (id(node), oidx, target)
        if key not in t_cache:
            t_cache[key] = _Node(
                _op(target), "%s_%s" % (node.name, target.strip("_")),
                {}, [entry], {"__mxc_opt__": "layout"})
        return (t_cache[key], 0)

    _CONVERT = {"conv": CONV_NHWC, "bn": BN_NHWC, "pool": POOL_NHWC}

    def replace(node, new_inputs, memo):
        in_region = id(node) in nhwc
        kind = _capability(node, shapes) if in_region else None
        if not in_region:
            # NCHW consumer: any input produced inside a region needs a
            # conversion back to NCHW at the border
            ins = [
                _wrap(e, TO_NCHW) if id(src) in nhwc else e
                for e, (src, _i) in zip(new_inputs, node.inputs)
            ]
            if all(a is b for (a, _), (b, _) in zip(ins, new_inputs)):
                return None  # default clone/share path
            return _Node(node.op, node.name, node.params, ins, node.attrs)

        def act(pos):
            """Activation operand at input slot pos, converted to NHWC."""
            src, _i = node.inputs[pos]
            e = new_inputs[pos]
            return e if id(src) in nhwc else _wrap(e, TO_NHWC)

        if kind == "conv":
            ins = [act(0)] + list(new_inputs[1:])  # weight/bias stay put
            return _Node(_op(CONV_NHWC), node.name, node.params, ins,
                         dict(node.attrs, __mxc_opt__="layout"))
        if kind == "bn":
            ins = [act(0)] + list(new_inputs[1:])
            return _Node(_op(BN_NHWC), node.name, node.params, ins,
                         dict(node.attrs, __mxc_opt__="layout"))
        if kind == "pool":
            return _Node(_op(POOL_NHWC), node.name, node.params, [act(0)],
                         dict(node.attrs, __mxc_opt__="layout"))
        # eltwise passthrough: every operand becomes NHWC
        ins = [act(p) for p in range(len(node.inputs))]
        return _Node(node.op, node.name, node.params, ins, node.attrs)

    new_sym = ir.rebuild(sym, replace)
    # heads produced inside a region leave the graph in NCHW (the
    # public output contract is layout-invariant)
    outs = []
    for (orig, i), entry in zip(sym._outputs, new_sym._outputs):
        outs.append(_wrap(entry, TO_NCHW) if id(orig) in nhwc else entry)
    return Symbol(outs), n_convs
