#!/usr/bin/env python3
"""Time of the grouped-product kernels alone, on the chip.

    python tools/gmm_probe.py [--shapes 65536x2304x896x16,...]
        [--plans default,512x1152x896,...] [--kinds gmm,gmm_t,tgmm]
        [--yardsticks ragged_dot,megablox] [--dtype bfloat16]
        [--results float32,bfloat16] [--scale] [--pair] [--iters 20]
        [--tag NAME] [--out chiprun_out/gmm_probe.jsonl]
        [--combine 8192x2304x8x64x16,...] [--combine-plans default,...]
        [--routings even,one]
        [--landed 0.125,0.25,...] [--rule landed|pad]
        [--layers mellum2,glm,kimi,laguna] [--root DIR]

For every shape ``MxKxNxG`` (rows, the right operand's two widths, groups)
it runs the three products a layer's gradient needs: ``gmm`` ([M, K] x
[G, K, N]), ``gmm_t`` ([M, N] x [G, K, N]^T, the input's cotangent) and
``tgmm`` ([M, K]^T x [M, N] per group, the weights' gradient), each at
every ``tm x tk x tn`` of ``--plans`` (``default`` = what
``grouped_matmul._plan`` picks for that result's type) and with its result
in every type of ``--results`` (the layer's forward products write
float32, its cotangents and weights' gradients the operands' type).
``--scale`` adds ``gmm`` with a row scale in its store (the down product's
forward); ``--pair`` adds ``gmm_t`` as ``moe_gmm_pair``, two products
summed into one result (the input's cotangent through gate and up: twice
the products, counted). Each runs ``--iters`` times back to back between two
fences on the host's clock (one kernel a call and nothing else on the
device, so that is its device time to a few microseconds), and prints
milliseconds a call and TFLOP/s over the 2 M K N products. Yardsticks at
the same operands: ``lax.ragged_dot`` (XLA's own kernel) and
``jax.experimental.pallas.ops.tpu.megablox`` at the plan's tiling. Rows
are split unevenly over the groups, one group empty, two fifths of the
rows in the last as ``moe_share_ffn`` sends its empty rows.

``--combine`` times ``moe_combine`` alone: for every ``NxDxKxExH``
(tokens, width, top k, experts, held) the bucket of ``share_bucket_rows``
rows that ``moe_share_ffn`` sorts under each routing of ``--routings``
(``even``: each token's top k drawn at random from all the experts;
``one``: every token's top k the first k experts, all held), its float32
rows summed back to the tokens at every ``TBxDC`` of ``--combine-plans``
(``default`` = ``_combine_plan``'s), with XLA's scatter-add
(``zeros.at[tok].add``) as the yardstick; GB/s over the rows read and
the tokens' rows written.

``--landed`` gives each share of the rows that land (``moe_share_ffn``'s
landed count over its bucket's rows): the groups of every product above
then add up to that share of ``M`` (``--rule landed``, the groups end at
the landed rows) or the rest of the rows ride in the last group (``--rule
pad``: the rule before the kernels stopped at the landed rows), and for
every cell of ``--layers`` one expert layer's value and gradient
(``moe_share_ffn`` at the cell's shapes, checkpointed with ``moe_sort``
and ``moe_hidden`` kept) is timed with a routing that lands that share of
its bucket, the products and sums inside it by the rule of the tree it
runs on. ``--root`` takes ``mxnet_tpu`` from another checkout (a parent
commit unpacked under ``.parent/``; run it with ``--rule pad``), so both
sides are read the same way in one chip call. No chip: exit 2, nothing
printed.
"""
import argparse
import functools
import itertools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="65536x2304x896x16,"
                    "65536x896x2304x16,16384x2304x1024x8,16384x1024x2304x8")
    ap.add_argument("--plans", default="default")
    ap.add_argument("--kinds", default="gmm,gmm_t,tgmm")
    ap.add_argument("--yardsticks", default="")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--results", default="float32")
    ap.add_argument("--scale", action="store_true")
    ap.add_argument("--pair", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--combine", default="")
    ap.add_argument("--combine-plans", default="default")
    ap.add_argument("--routings", default="even,one")
    ap.add_argument("--landed", default="")
    ap.add_argument("--rule", default="landed", choices=("landed", "pad"))
    ap.add_argument("--layers", default="")
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "gmm_probe.jsonl"))
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return 2
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    dtype = jnp.dtype(args.dtype)
    rows = []

    def timed(fn, *operands):
        jax.block_until_ready(fn(*operands))
        start = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*operands)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - start) / args.iters

    def report(row, flop, fn=None, *operands):
        """One line: the time of ``fn``, or the ``error`` the row came with."""
        try:
            if fn is not None:
                row["ms"] = timed(fn, *operands)
                if row.get("kind") == "combine":  # bytes, not products
                    row["gbps"] = flop / row["ms"] / 1e6
                else:
                    row["tflops"] = flop / row["ms"] / 1e9
        except Exception as e:  # a refused plan must not end the sweep
            row["error"] = "%s: %s" % (type(e).__name__, str(e)[-300:])
        rows.append(row)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    landed = [float(x) for x in filter(None, args.landed.split(","))]
    for shape, fill in itertools.product(
            filter(None, args.shapes.split(",")), landed or [None]):
        m, k, n, g = (int(x) for x in shape.split("x"))
        rng = np.random.RandomState(0)
        share = rng.dirichlet(np.ones(g) * 0.5)
        share[g // 2] = 0.0
        rows_in = m if fill is None else int(fill * m)
        sizes = np.floor(share / share.sum() * 0.6 * rows_in).astype(np.int32)
        sizes[-1] += (m if fill is None or args.rule == "pad" else rows_in) \
            - sizes.sum()
        sizes = jnp.asarray(sizes)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        lhs = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
        rhs = jax.random.normal(keys[1], (g, k, n), jnp.float32).astype(dtype)
        out = jax.random.normal(keys[2], (m, n), jnp.float32).astype(dtype)
        scale = jax.random.uniform(keys[2], (m,), jnp.float32)
        operands = {"gmm": (lhs, rhs), "gmm_t": (out, rhs),
                    "tgmm": (lhs, out), "gmm_scaled": (lhs, rhs, scale),
                    "gmm_t_pair": (out, rhs, out, rhs)}
        kinds = args.kinds.split(",")
        kinds += ["gmm_scaled"] * args.scale + ["gmm_t_pair"] * args.pair
        for kind, result in itertools.product(kinds,
                                              args.results.split(",")):
            kernel = {"tgmm": "moe_tgmm", "gmm_t_pair": "moe_gmm_pair"}.get(
                kind, "moe_gmm")
            back = kind.startswith("gmm_t")
            wide = (m, n, k) if back else (m, k, n)
            result = jnp.dtype(result)
            for plan in args.plans.split(","):
                if plan == "default":
                    tiles, refusal = gm._plan(
                        *wide, g, dtype.itemsize, kernel,
                        out_itemsize=result.itemsize,
                        scaled=kind == "gmm_scaled")
                else:
                    tiles, refusal = tuple(
                        int(x) for x in plan.split("x")), None
                row = {"tag": args.tag, "shape": shape, "kind": kind,
                       "plan": tiles, "dtype": dtype.name,
                       "result": result.name,
                       "device_kind": dev.device_kind}
                if fill is not None:
                    row.update(landed=fill, rule=args.rule)
                flop = 2.0 * m * k * n * (2 if kind == "gmm_t_pair" else 1)
                if refusal is not None:
                    report(dict(row, error=refusal), flop)
                    continue
                if any(w % t for w, t in zip(wide, tiles)):
                    continue  # another kind's plan

                def run(*ops, kind=kind, kernel=kernel, tiles=tiles,
                        back=back, result=result):
                    if kind == "gmm_scaled":
                        return gm._product(kernel, ops[:2], sizes, tiles,
                                           result, scale=ops[2])
                    return gm._product(kernel, ops, sizes, tiles, result,
                                       transposed=back)

                report(row, flop, jax.jit(run), *operands[kind])
                if kind in ("gmm_scaled", "gmm_t_pair"):
                    continue  # the yardsticks have no such product
                for yard in filter(None, args.yardsticks.split(",")):
                    report(dict(row, yardstick=yard), flop,
                           jax.jit(_yardstick(yard, kind, tiles, sizes)),
                           *operands[kind])
    for shape in filter(None, args.combine.split(",")):
        for routing in args.routings.split(","):
            for row, moved, fn, operands in _combines(
                    shape, routing, args.combine_plans.split(",")):
                report(dict(row, tag=args.tag, device_kind=dev.device_kind),
                       moved, fn, *operands)
    for cell, fill in itertools.product(
            filter(None, args.layers.split(",")), landed):
        row, flop, fn, operands = _layer(cell, fill)
        report(dict(row, tag=args.tag, rule=args.rule,
                    device_kind=dev.device_kind), flop, fn, *operands)
    return 0 if all("error" not in row for row in rows) else 1


#: tokens, width, an expert's width, top k, experts, held, router, shared
#: experts: the expert layer of the benchmark's four MoE cells
LAYERS = {"mellum2": (8192, 2304, 896, 8, 64, 16, "softmax", 0),
          "glm": (8192, 2048, 1536, 4, 64, 8, "sigmoid", 1),
          "kimi": (8192, 2304, 1024, 8, 256, 8, "sigmoid", 1),
          "laguna": (8192, 2048, 512, 8, 256, 32, "sigmoid", 1)}


def _layer(cell, fill):
    """(row, the grouped products' flops at the landed rows, function,
    operands) of one expert layer's value and gradient at ``cell``'s
    shapes with ``fill`` of its bucket landed: the router reads the first
    ``experts`` channels as logits, so that the tokens that land choose
    ``top_k`` held experts each (in turn) and the others ``top_k`` experts
    held elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel import moe

    tokens, d, ff, top_k, experts, held, score, shared = LAYERS[cell]
    bucket = moe.share_bucket_rows(tokens, experts, (0, held), top_k)
    landing = int(fill * bucket) // top_k
    params = moe.init_share_params(jax.random.PRNGKey(0), experts,
                                   (0, held), d, ff, shared, score=score)
    params["router"] = jnp.eye(d, experts, dtype=jnp.float32)
    if "router_bias" in params:
        params["router_bias"] = jnp.zeros_like(params["router_bias"])
    logits = np.full((tokens, experts), -4.0, np.float32)
    for t in range(tokens):
        if t < landing:
            chosen = (t + np.arange(top_k)) % held
        else:
            chosen = held + (t + np.arange(top_k)) % (experts - held)
        logits[t, chosen] = 4.0 - 0.01 * np.arange(top_k)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d), jnp.float32)
    x = x.at[:, :experts].set(jnp.asarray(logits))
    # three forward products, the down product rebuilt unscaled for the
    # routing weight's cotangent, and two products in each one's backward
    flop = 2.0 * landing * top_k * d * ff * 10
    row = {"layer": cell, "kind": "layer", "landed": fill,
           "landed_rows": landing * top_k, "bucket": bucket}
    return row, flop, _layer_step(cell), (params, x)


@functools.lru_cache(maxsize=None)
def _layer_step(cell):
    """The jitted value and gradient of ``cell``'s expert layer: one
    compile for every landed share."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    _, _, _, top_k, _, held, score, _ = LAYERS[cell]
    layer = jax.checkpoint(
        lambda p, x: moe.moe_share_ffn(p, x, top_k, (0, held),
                                       dtype="bfloat16", score=score)[0],
        policy=jax.checkpoint_policies.save_only_these_names(
            "moe_sort", "moe_hidden"))
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(layer(p, x) ** 2), argnums=(0, 1)))


def _combines(shape, routing, plans):
    """(row, bytes moved, function, operands) of each plan of
    ``moe_combine`` and of the scatter-add at one shape and routing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.parallel import moe

    n, d, top_k, experts, held = (int(x) for x in shape.split("x"))
    rows = moe.share_bucket_rows(n, experts, (0, held), top_k)
    rng = np.random.RandomState(0)
    if routing == "one":
        idx = np.tile(np.arange(top_k), (n, 1))
    else:
        idx = np.argsort(rng.rand(n, experts), axis=1)[:, :top_k]
    key = np.where(idx < held, idx, held).reshape(-1)
    tok = jnp.asarray(np.argsort(key, kind="stable")[:rows] // top_k,
                      jnp.int32)
    values = jax.random.normal(jax.random.PRNGKey(0), (rows, d), jnp.float32)
    moved = 4.0 * d * (rows + n)
    row = {"shape": shape, "kind": "combine", "routing": routing,
           "rows": rows}
    for plan in plans:
        if plan == "default":
            tiles, limit, refusal = gm._combine_plan(n, d, rows, jnp.float32)
        else:
            tiles, refusal = tuple(int(x) for x in plan.split("x")), None
            if rows % tiles[0] or d % tiles[1]:
                continue  # another shape's plan
            mib = 1024 * 1024
            need = gm._combine_vmem(n, *tiles)
            limit = (-(-need // mib) * mib if need > gm._pk._VMEM_LIMIT
                     else None)
        if refusal is not None:
            yield dict(row, plan=plan, error=refusal), moved, None, ()
            continue
        call = gm._combine_call(n, d, rows, tiles, limit, False)
        yield (dict(row, plan=tiles, vmem_limit=limit), moved,
               lambda v, t, call=call: call(
                   v, t, jnp.full((1,), rows, jnp.int32)),
               (values, tok))
    yield (dict(row, yardstick="scatter_add"), moved,
           jax.jit(lambda v, t: jnp.zeros((n, d), v.dtype).at[t].add(v)),
           (values, tok))


def _yardstick(yard, kind, tiles, sizes):
    """The same product by ``lax.ragged_dot`` (through its own gradient
    rules) or by megablox at ``tiles``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    if yard == "megablox":
        import importlib

        mb = importlib.import_module(  # the package's ``gmm`` is a function
            "jax.experimental.pallas.ops.tpu.megablox.gmm")

        if kind == "tgmm":
            return lambda a, b: mb.tgmm(a.T, b, sizes, f32, tiles)
        return lambda a, b: mb.gmm(a, b, sizes, f32, tiles,
                                   transpose_rhs=kind == "gmm_t")

    def product(a, b):
        return lax.ragged_dot(a, b, sizes, preferred_element_type=f32)

    if kind == "gmm":
        return product
    if kind == "gmm_t":  # (cotangent, rhs) -> the left operand's gradient
        return lambda out, b: jax.vjp(
            lambda a: product(a, b),
            jnp.zeros((out.shape[0], b.shape[1]), b.dtype))[1](
                out.astype(f32))[0]
    return lambda a, out: jax.vjp(
        lambda b: product(a, b), jnp.zeros(
            (sizes.shape[0], a.shape[1], out.shape[1]), a.dtype))[1](
                out.astype(f32))[0]


if __name__ == "__main__":
    sys.exit(main())
