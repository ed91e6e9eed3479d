"""Plain reference for the ``mellum2-12b-a2.5b`` configuration.

Mellum2-12B-A2.5B (the Hugging Face ``config.json`` named in
``configs/mellum2-12b-a2.5b.json``): a pre-norm decoder with RMS norm, an
untied head and, in every layer, grouped-query softmax attention under rotary
positions followed by a sparse expert layer:

* **attention, both kinds**: ``q = x Wq`` (32 heads of 128), ``k = x Wk``,
  ``v = x Wv`` (4 heads of 128), no biases, no norm on q or k; q and k rotated
  (below); query head ``h`` reads key/value head ``h // 8``; scores
  ``q.k / sqrt(128)``, causal softmax, ``o Wo``. Here k and v are REPEATED per
  group and the softmax is a plain masked one, computed in blocks of rows.
* **sliding_attention** (three layers in four): position ``i`` sees ``j`` with
  ``i - 1024 < j <= i`` (``min(i + 1, 1024)`` keys, itself included). Rotation:
  ``inv_freq_m = theta ** (-2 m / 128)``, ``m = 0..63``, theta 500,000; cos and
  sin of ``pos * inv_freq`` laid twice side by side over the 128 channels;
  ``x' = x cos + [-x[64:], x[:64]] sin``.
* **full_attention** (the fourth): every ``j <= i``. Rotation: YaRN, static at
  every length: ``dim(r) = 128 ln(L0 / (2 pi r)) / (2 ln theta)`` with ``L0`` the
  original 8,192 positions, ``low = floor(dim(beta_fast))``,
  ``high = ceil(dim(beta_slow))``, ``ramp_m = clip((m - low) / (high - low), 0,
  1)``, ``inv_freq_m = (1 - ramp_m) theta^(-2m/128) + ramp_m theta^(-2m/128) /
  factor``; cos and sin both times ``attention_factor``.
* **MoE** (every layer): ``p = softmax(x Wg)`` over all 64 experts, the 8
  largest, weights ``p_i / sum_chosen(p)``, ``y = sum_i w_i E_i(x)``, ``E(x) =
  Wdown(SiLU(Wgate x) * Wup x)`` at width 896; no bias, no shared expert. THIS
  CHIP'S SHARE: only the experts ``experts_held`` are here; the layer gives
  their part of the sum and that partial result goes on (the guide's cut, in
  the program and here alike). A loop over the held experts under a mask; held
  = all of them is the uncut layer (the share test).

Straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``. It imports nothing of ``mxnet_tpu``
and takes nothing the program has made: the weights are drawn here from the
seed and the driver hands the same draw to the program.

``quant``: ``None`` is the reference; ``"fp8"`` is the control: every operand
the configuration's ``precision`` states as bfloat16 (of every projection, of
the experts and the head, of attention's q.k and p.v) is rounded to
float8_e4m3 by tensor (``lowprec.quantiser``); what it states as float32 (the
residual stream, norms, the rotation, router probabilities, softmax, loss,
masters and optimizer state) stays float32. Three more names plant this
model's own faults in the float32 reference, for ``calibrate.py`` to read them
at the cell's size beside the control (``EXTRA_CONTROLS``):
``"window_left_out"`` (the window layers attend to every earlier key),
``"yarn_left_out"`` (the full layer rotates as the window layers do) and
``"assignments_dropped"`` (a capacity of ``DROP_CAPACITY`` of an expert's mean
load; what overflows it, in token order, is dropped).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import lowprec
import traffic

ADAM = dict(b1=0.9, b2=0.999, eps=1e-8)
#: rows of a block of attention's score matrix
ROW_BLOCK = 256
#: faults ``calibrate.py`` reads besides the ``"fp8"`` control
EXTRA_CONTROLS = ("window_left_out", "yarn_left_out", "assignments_dropped")
DROP_CAPACITY = 0.75
KINDS = {"sliding_attention": "swa", "full_attention": "full"}


def sizes(config):
    """The sizes this file computes with, from the configuration's file."""
    L = int(config["num_layers"])
    lo, hi = (int(i) for i in config["experts_held"])
    published = config.get("published", {})
    if set(config["mlp_layer_types"][:L]) != {"sparse"}:
        raise ValueError("a layer that is not sparse: %s"
                         % config["mlp_layer_types"][:L])
    return dict(
        d=int(config["hidden_size"]), L=L, V=int(config["vocab_size"]),
        eps=float(config["rms_norm_eps"]),
        kinds=tuple(KINDS[k] for k in config["layer_types"][:L]),
        mlps=("moe",) * L,
        H=int(config["num_attention_heads"]),
        G=int(config["num_key_value_heads"]), D=int(config["head_dim"]),
        window=int(config["sliding_window"]),
        rope={KINDS[k]: dict(v) for k, v in config["rope_parameters"].items()},
        eff=int(config["moe_intermediate_size"]),
        E=int(published.get("num_experts", config["num_experts"])),
        held=(lo, hi), top_k=int(config["num_experts_per_tok"]),
        renormalize=bool(config["norm_topk_prob"]))


# -- the configuration's own count of a step's work --------------------------------


def window_pairs(T, W):
    """(query, key) pairs a window of ``W`` lets through at length ``T``:
    position ``i`` sees ``min(i + 1, W)`` keys."""
    W = min(W, T)
    return W * (W + 1) / 2.0 + (T - W) * W


def _forward_flops_per_token(sz, T):
    """Matrix products of one token's forward pass (2 a multiply-add), as
    the algorithm needs them: nothing recomputed, attention over the pairs
    its mask lets through, the routed experts at the assignments that LAND
    here in expectation (top_k x held / published a token), never the sorted
    bucket's padding."""
    d, H, G, D = sz["d"], sz["H"], sz["G"], sz["D"]
    proj = 2 * d * H * D + 2 * 2 * d * G * D + 2 * H * D * d
    pairs = {"full": T * (T + 1) / 2.0, "swa": window_pairs(T, sz["window"])}
    here = sz["top_k"] * (sz["held"][1] - sz["held"][0]) / float(sz["E"])
    moe = 2 * d * sz["E"] + here * 3 * 2 * d * sz["eff"]
    total = 2 * d * sz["V"]
    for kind in sz["kinds"]:
        total += proj + H * 4 * D * pairs[kind] / T + moe
    return total


def train_flops(config, mix):
    """Model FLOPs of one training step (forward and backward, nothing
    recomputed): what ``mfu.train`` divides."""
    T = int(mix["seq_len"])
    return 3 * int(mix["batch"]) * T * _forward_flops_per_token(
        sizes(config), T)


def _attention_work(config, mix, kind, pairs):
    """(FLOPs, least bytes) of one step's softmax attention in the layers of
    ``kind``, forward and backward: ``pairs`` x (2 x 128 + 2 x 128) a query
    head forward, twice that backward; forward reads q, k, v and writes o,
    backward reads q, k, v, o, do and writes dq, dk, dv, 2 bytes each, q, o,
    do, dq at the query heads and k, v, dk, dv at the key/value heads."""
    sz = sizes(config)
    B, T = int(mix["batch"]), int(mix["seq_len"])
    layers = sum(k == kind for k in sz["kinds"])
    work = 3 * B * sz["H"] * pairs * 4 * sz["D"]
    nbytes = B * T * sz["D"] * 2 * 6 * (sz["H"] + sz["G"])
    return layers * work, layers * nbytes


def attention_work(config, mix):
    """Of the full-attention layers (the kernels ``flash_fwd``,
    ``flash_bwd_dq``, ``flash_bwd_dkv``): causal pairs ``T (T + 1) / 2``."""
    T = int(mix["seq_len"])
    return _attention_work(config, mix, "full", T * (T + 1) / 2.0)


def window_attention_work(config, mix):
    """Of the window layers (the kernels ``flash_win_*``): the pairs the
    window lets through, ``W (W + 1) / 2 + (T - W) W``."""
    return _attention_work(config, mix, "swa", window_pairs(
        int(mix["seq_len"]), sizes(config)["window"]))


# -- weights from the seed ---------------------------------------------------------


def _draw(sz, key):
    """The weights, float32, as the program holds them: a list of layers.
    Normal at 0.02 for the embedding, 1/sqrt(fan-in) for every projection,
    the router and the head; norms 1."""
    d = sz["d"]
    keys = iter(jax.random.split(key, 16 * (sz["L"] + 1)))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def attention():
        q, kv = sz["H"] * sz["D"], sz["G"] * sz["D"]
        return {"wq": dense((d, q)), "wk": dense((d, kv)),
                "wv": dense((d, kv)), "wo": dense((q, d))}

    def moe_layer():
        n = (sz["held"][1] - sz["held"][0],)
        return {"router": dense((d, sz["E"])),
                "experts": {"w_gate": dense(n + (d, sz["eff"])),
                            "w_up": dense(n + (d, sz["eff"])),
                            "w_down": dense(n + (sz["eff"], d))}}

    layers = [{"norm1": jnp.ones((d,), jnp.float32), "attn": attention(),
               "norm2": jnp.ones((d,), jnp.float32), "mlp": moe_layer()}
              for _ in range(sz["L"])]
    return {"embed": dense((sz["V"], d), 0.02), "layers": layers,
            "norm_f": jnp.ones((d,), jnp.float32),
            "lm_head": dense((d, sz["V"]))}


def make_params(config, seed):
    """One jitted call: the model's float32 weights from the seed."""
    sz = sizes(config)
    return jax.jit(lambda key: _draw(sz, key))(traffic.key_of(seed))


def leaf_names(config):
    """Names of the leaves in the order of :func:`leaf_norms`."""
    sz = sizes(config)
    shapes = jax.eval_shape(lambda k: _draw(sz, k), jax.random.PRNGKey(0))
    paths, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path) for path, _ in paths]


def leaf_norms(tree):
    """Euclidean norm of every leaf, float32, in ``jax.tree`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


# -- the model ---------------------------------------------------------------------


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_freq(rope, D):
    """``(inv_freq [D / 2] float32, factor)`` of one layer kind's rotation,
    from its entry of ``rope_parameters``: the plain one, or YaRN's."""
    half = D // 2
    theta = float(rope["rope_theta"])
    plain = np.power(theta, -np.arange(half, dtype=np.float64) / half)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError("rotation %r" % rope["rope_type"])
    length = float(rope["original_max_position_embeddings"])

    def dim(turns):
        return D * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rope["beta_slow"]))), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    scaled = (1.0 - ramp) * plain + ramp * plain / float(rope["factor"])
    return scaled.astype(np.float32), float(rope["attention_factor"])


def rotate(x, rope, D):
    """x [B, T, heads, D] turned by its positions 0..T - 1."""
    freq, factor = inv_freq(rope, D)
    T = x.shape[1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return (x * (jnp.cos(angle) * factor)
            + turned * (jnp.sin(angle) * factor))


def attention(x, p, sz, kind, q=lambda a: a, window=True, yarn=True):
    """One attention layer over the block's normed input x [B, T, d].
    ``window`` / ``yarn`` off plant the faults: a window layer that sees
    every earlier key, a full layer that turns by the plain rotation."""
    B, T, _ = x.shape
    H, G, D = sz["H"], sz["G"], sz["D"]
    rope = sz["rope"][kind if yarn else "swa"]
    qh = rotate((q(x) @ q(p["wq"])).reshape(B, T, H, D), rope, D)
    kh = rotate((q(x) @ q(p["wk"])).reshape(B, T, G, D), rope, D)
    vh = (q(x) @ q(p["wv"])).reshape(B, T, G, D)
    # every query head its own copy of the key/value head it reads
    kh, vh = (jnp.repeat(t, H // G, axis=2) for t in (kh, vh))
    qh, kh, vh = q(qh), q(kh), q(vh)
    rows = ROW_BLOCK if T % ROW_BLOCK == 0 else T
    reach = sz["window"] if kind == "swa" and window else T

    @jax.checkpoint
    def block(args):
        q_rows, row0 = args  # [B, rows, H, D]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_rows, kh) * D ** -0.5
        iq = row0 + jnp.arange(rows)[:, None]
        ik = jnp.arange(T)[None, :]
        s = jnp.where((ik <= iq) & (ik > iq - reach), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", q(jax.nn.softmax(s, axis=-1)),
                          vh)

    q_blocks = jnp.moveaxis(qh.reshape(B, T // rows, rows, H, D), 1, 0)
    o = lax.map(block, (q_blocks, jnp.arange(T // rows) * rows))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * D)
    return q(o) @ q(p["wo"])


def _expert(x, p, q):
    return q(jax.nn.silu(q(x) @ q(p["w_gate"])) * (q(x) @ q(p["w_up"]))) @ q(
        p["w_down"])


def route(x, p, sz):
    """Probabilities over ALL experts, the chosen ones and their weights:
    idx, w [..., top_k] (float32; never quantised)."""
    prob = jax.nn.softmax(x @ p["router"], axis=-1)
    chosen, idx = lax.top_k(prob, sz["top_k"])
    if sz["renormalize"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, chosen


def moe(x, p, sz, q=lambda a: a, capacity=None):
    """The expert layer's part that the experts ``sz["held"]`` give: a loop
    over the held experts, each over every token under a mask. Returns (y,
    assignments per held expert). ``capacity`` (the planted fault):
    assignments an expert takes, in token order."""
    idx, w = route(x, p, sz)
    lo, hi = sz["held"]

    @jax.checkpoint
    def one(y, args):
        e, pe = args
        here = idx == e
        if capacity is not None:
            named = jnp.any(here, axis=-1).reshape(-1)
            kept = (jnp.cumsum(named) <= capacity).reshape(here.shape[:-1])
            here = here & kept[..., None]
        mine = jnp.sum(jnp.where(here, w, 0.0), axis=-1)
        return y + mine[..., None] * _expert(x, pe, q), jnp.sum(here)

    return lax.scan(one, jnp.zeros_like(x), (jnp.arange(lo, hi),
                                             p["experts"]))


def forward(params, tokens, sz, quant=None):
    """tokens [B, T] -> (logits [B, T, V] float32, routing counts
    [layers, held])."""
    q = lowprec.quantiser(None if quant in EXTRA_CONTROLS else quant)
    capacity = None
    if quant == "assignments_dropped":
        capacity = DROP_CAPACITY * tokens.size * sz["top_k"] / sz["E"]
    x = params["embed"][tokens]
    counts = []
    for lp, kind in zip(params["layers"], sz["kinds"]):

        @jax.checkpoint
        def block(x, lp, kind=kind):
            h = _rms_norm(x, lp["norm1"], sz["eps"])
            x = x + attention(h, lp["attn"], sz, kind, q,
                              window=quant != "window_left_out",
                              yarn=quant != "yarn_left_out")
            h = _rms_norm(x, lp["norm2"], sz["eps"])
            y, n = moe(h, lp["mlp"], sz, q, capacity=capacity)
            return x + y, n

        x, n = block(x, lp)
        counts.append(n)
    x = _rms_norm(x, params["norm_f"], sz["eps"])
    return q(x) @ q(params["lm_head"]), jnp.stack(counts)


def loss_fn(params, tokens, sz, quant=None):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1], float32."""
    logits, _ = forward(params, tokens[:, :-1], sz, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, tokens[:, 1:, None], axis=-1)[..., 0])


def loss_and_grad(params, tokens, sz, quant=None):
    return jax.value_and_grad(loss_fn)(params, tokens, sz, quant)


def train_readings(config, seed, batches, lr, quant=None,
                   keep_positions=None):
    """The numbers a training cell compares, from the reference: the loss
    of each of the first steps, the norm of the first gradient and of the
    parameters' change after them, leaf by leaf. ``batches`` [steps, B,
    T + 1]. One jitted Adam step, called once a batch. ``keep_positions``
    plants the half-batch fault (the batch is one row: the first positions
    are kept and the mean is over them)."""
    sz = sizes(config)
    lr = float(lr)

    def step(params, mu, nu, tokens, t):
        if keep_positions is not None:
            tokens = tokens[:, :keep_positions + 1]
        loss, grad = loss_and_grad(params, tokens, sz, quant)
        mu = jax.tree.map(
            lambda m, g: ADAM["b1"] * m + (1 - ADAM["b1"]) * g, mu, grad)
        nu = jax.tree.map(
            lambda v, g: ADAM["b2"] * v + (1 - ADAM["b2"]) * g * g, nu, grad)
        c1, c2 = 1 - ADAM["b1"] ** t, 1 - ADAM["b2"] ** t
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (
                jnp.sqrt(v / c2) + ADAM["eps"]), params, mu, nu)
        return params, mu, nu, loss, leaf_norms(grad)

    with jax.default_matmul_precision("highest"):
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        params = make_params(config, seed)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for i, tokens in enumerate(np.asarray(batches)):
            params, mu, nu, loss, norms = step(
                params, mu, nu, jnp.asarray(tokens), jnp.float32(i + 1))
            losses.append(float(loss))
            first = np.asarray(norms, np.float64) if first is None else first
        del mu, nu
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(
                params, make_params(config, seed))
    return dict(loss=np.asarray(losses, np.float64), grad_norm=first,
                change_norm=np.asarray(change, np.float64))
