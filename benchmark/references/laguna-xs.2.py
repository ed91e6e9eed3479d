"""Plain reference for the ``laguna-xs.2`` configuration.

Laguna-XS.2 (``model_type laguna``; the Hugging Face ``config.json`` named in
``configs/laguna-xs.2.json``): a pre-norm decoder with RMS norm, an untied
head and, in every layer, grouped-query softmax attention under rotary
positions with a gated output, then a dense gated MLP (layer 0) or a sparse
expert layer with a shared expert (every later layer):

* **attention, both kinds**: ``q = x Wq`` (``H`` heads of 128: 48 in a full
  layer, 64 in a window layer, ``num_attention_heads_per_layer``), ``k = x
  Wk``, ``v = x Wv`` (8 heads of 128), no biases, no norm on q or k; q and k
  rotated (below); query head ``h`` reads key/value head ``h // (H / 8)``;
  scores ``q.k / sqrt(128)``, causal softmax, ``o = p v``; **the output
  gate** (``gating``, read as G1 of Qiu et al., arXiv:2505.06708:
  ``assumed.gating``): ``o <- o * sigmoid(x Wg)``, elementwise over the ``H x
  128`` channels, ``x`` the block's normed input; then ``o Wo``. Here k and
  v are REPEATED per group and the softmax is a plain masked one, computed in
  blocks of rows.
* **sliding_attention** (layers 1-3 here): position ``i`` sees ``j`` with
  ``i - 512 < j <= i``. Rotation: the plain one over all 128 channels
  (``partial_rotary_factor`` 1): ``inv_freq_m = 10000 ** (-2 m / 128)``, ``m
  = 0..63``, cos and sin of ``pos * inv_freq`` laid twice side by side,
  ``x' = x cos + [-x[64:], x[:64]] sin``.
* **full_attention** (layers 0 and 4 here): every ``j <= i``. Rotation:
  YaRN over the FIRST ``r = 128 x 0.5 = 64`` channels of each head only; the
  other 64 pass through unturned. Over those 64: ``dim(t) = 64 ln(L0 / (2 pi
  t)) / (2 ln theta)`` with ``theta`` 500,000 and ``L0`` the original 4,096
  positions, ``low = floor(dim(beta_fast = 64))`` = 5, ``high =
  ceil(dim(beta_slow = 1))`` = 16, ``ramp_m = clip((m - low) / (high - low),
  0, 1)``, ``inv_freq_m = (1 - ramp_m) theta^(-2m/64) + ramp_m
  theta^(-2m/64) / 64``, ``m = 0..31``; channel ``m`` pairs with ``m + 32``
  (``assumed.rope_pairing``); cos and sin both times ``attention_factor``
  1.4158883.
* **dense MLP** (layer 0): ``Wdown(SiLU(Wgate x) * Wup x)`` at width 8,192.
* **MoE** (every later layer): ``s = sigmoid(x Wg)`` over all 256 experts,
  the 8 largest of ``s + b`` (``b`` a buffer no gradient reaches), weights
  ``2.5 s_i / sum_chosen(s)`` (``assumed.router``), plus one shared expert;
  every expert is ``Wdown(SiLU(Wgate x) * Wup x)`` at width 512. THIS CHIP'S
  SHARE: only the experts ``experts_held`` are here; the block gives their
  part of the sum and the shared expert, and that partial result goes on
  (the guide's cut, in the program and here alike). A loop over the held
  experts with a mask; held = all of them is the uncut layer (the share
  test).

Straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``. It imports nothing of ``mxnet_tpu``
and takes nothing the program has made: the weights are drawn here from the
seed and the driver hands the same draw to the program.

``quant``: ``None`` is the reference; ``"fp8"`` is the control: every operand
the configuration's ``precision`` states as bfloat16 (of every projection,
the gate's included, of the experts, the dense MLP and the head, of
attention's q.k and p.v) is rounded to float8_e4m3 by tensor
(``lowprec.quantiser``); what it states as float32 (the residual stream,
norms, the rotation, the gate's sigmoid and product, router scores, softmax,
loss, masters and optimizer state) stays float32. Six more names plant this
model's own faults in the float32 reference, for ``calibrate.py`` to read
them at the cell's size beside the control (``EXTRA_CONTROLS``):
``"window_left_out"`` (the window layers attend to every earlier key),
``"yarn_left_out"`` (the full layers rotate as the window layers do: all
128 channels, the plain rotation at 10,000), ``"assignments_dropped"`` (a
capacity of ``DROP_CAPACITY`` of an expert's mean load; what overflows it,
in token order, is dropped), ``"gate_left_out"`` (no output gate),
``"rotary_whole"`` (the full layers' YaRN over all 128 channels, as if
``partial_rotary_factor`` were 1) and ``"shared_left_out"`` (the shared
expert's term is dropped).
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
EXTRA_CONTROLS = ("window_left_out", "yarn_left_out", "assignments_dropped",
                  "gate_left_out", "rotary_whole", "shared_left_out")
DROP_CAPACITY = 0.75
KINDS = {"sliding_attention": "swa", "full_attention": "full"}
MLPS = {"dense": "dense", "sparse": "moe"}


def sizes(config):
    """The sizes this file computes with, from the configuration's file."""
    L = int(config["num_layers"])
    lo, hi = (int(i) for i in config["experts_held"])
    published = config.get("published", {})
    kinds = tuple(KINDS[k] for k in config["layer_types"][:L])
    heads = {}
    for kind, h in zip(kinds, config["num_attention_heads_per_layer"][:L]):
        if heads.setdefault(kind, int(h)) != int(h):
            raise ValueError("%s layers with %d and %d heads"
                             % (kind, heads[kind], int(h)))
    D = int(config["head_dim"])
    rope = {KINDS[k]: dict(config["rope_parameters"][k]) for k in KINDS}
    eff = int(config["moe_intermediate_size"])
    shared = int(config["shared_expert_intermediate_size"])
    if shared % eff:
        raise ValueError("a shared expert %d wide over experts %d wide"
                         % (shared, eff))
    assumed = config["assumed"]
    return dict(
        d=int(config["hidden_size"]), L=L, V=int(config["vocab_size"]),
        eps=float(config["rms_norm_eps"]), kinds=kinds,
        mlps=tuple(MLPS[m] for m in config["mlp_layer_types"][:L]),
        H=heads, G=int(config["num_key_value_heads"]), D=D,
        window=int(config["sliding_window"]), rope=rope,
        rotary={k: int(D * float(r.get("partial_rotary_factor", 1.0)))
                for k, r in rope.items()},
        gate=bool(config["gating"]),
        ff=int(config["intermediate_size"]), eff=eff, shared=shared // eff,
        E=int(published.get("num_experts", config["num_experts"])),
        held=(lo, hi), top_k=int(config["num_experts_per_tok"]),
        route_scale=float(config["moe_routed_scaling_factor"]),
        renormalize=bool(assumed["norm_topk_prob"]),
        # not the reference's (it rebuilds what it likes): what the
        # program is told to keep for its backward pass
        kept=tuple(assumed["kept"]))


# -- the configuration's own count of a step's work --------------------------------


def window_pairs(T, W):
    """(query, key) pairs a window of ``W`` lets through at length ``T``:
    position ``i`` sees ``min(i + 1, W)`` keys."""
    W = min(W, T)
    return W * (W + 1) / 2.0 + (T - W) * W


def _forward_flops_per_token(sz, T):
    """Matrix products of one token's forward pass (2 a multiply-add), as
    the algorithm needs them: nothing recomputed, attention over the pairs
    its mask lets through, the gate's product, the routed experts at the
    assignments that LAND here in expectation (top_k x held / published a
    token), the shared expert, never the sorted bucket's padding."""
    d, G, D = sz["d"], sz["G"], sz["D"]
    pairs = {"full": T * (T + 1) / 2.0, "swa": window_pairs(T, sz["window"])}
    here = sz["top_k"] * (sz["held"][1] - sz["held"][0]) / float(sz["E"])
    expert = 3 * 2 * d * sz["eff"]
    mlp = {"dense": 3 * 2 * d * sz["ff"],
           "moe": 2 * d * sz["E"] + (sz["shared"] + here) * expert}
    total = 2 * d * sz["V"]
    for kind, m in zip(sz["kinds"], sz["mlps"]):
        H = sz["H"][kind]
        proj = 2 * d * H * D * (3 if sz["gate"] else 2) + 2 * 2 * d * G * D
        total += proj + H * 4 * D * pairs[kind] / T + mlp[m]
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
    do, dq at the kind's query heads and k, v, dk, dv at the key/value
    heads."""
    sz = sizes(config)
    B, T = int(mix["batch"]), int(mix["seq_len"])
    layers = sum(k == kind for k in sz["kinds"])
    H = sz["H"].get(kind, 0)
    work = 3 * B * H * pairs * 4 * sz["D"]
    nbytes = B * T * sz["D"] * 2 * 6 * (H + sz["G"])
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


def expert_work(config, mix, landed_rows):
    """(FLOPs, least bytes) of one step's grouped products of the routed
    experts (the kernels ``moe_gmm``, ``moe_gmm_pair``, ``moe_tgmm``) over
    ``landed_rows``, the assignments that LANDED on the held experts in a
    step, summed over the expert layers (the step's routing counts; the
    sorted bucket's other rows are time and no work). FLOPs: gate, up and
    down forward, and for each the input's cotangent and the weights'
    gradient backward, ``3 x 3 x 2 x rows x d x eff``. Least bytes, 2 a
    value: forward reads the landed rows and every held expert's three
    matrices and writes the rows' output; backward reads the output's
    cotangent, the rows and the matrices and writes the rows' cotangent and
    the matrices' gradients, ``2 (5 rows d + 9 layers held d eff)``: what
    any implementation moves at the least, so no program can read over 100
    %."""
    sz = sizes(config)
    d, eff = sz["d"], sz["eff"]
    rows = float(landed_rows)
    layers = sum(m == "moe" for m in sz["mlps"])
    held = sz["held"][1] - sz["held"][0]
    work = 3 * 3 * 2 * rows * d * eff
    nbytes = 2 * (5 * rows * d + 9 * layers * held * d * eff)
    return work, nbytes


# -- weights from the seed ---------------------------------------------------------


def _draw(sz, key):
    """The weights, float32, as the program holds them: a list of layers.
    Normal at 0.02 for the embedding, 1/sqrt(fan-in) for every projection,
    the gate, the router and the head; norms 1; the router's
    score-correction bias normal at 0.02."""
    d, D, G = sz["d"], sz["D"], sz["G"]
    keys = iter(jax.random.split(key, 16 * (sz["L"] + 1)))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def attention(kind):
        q = sz["H"][kind] * D
        p = {"wq": dense((d, q)), "wk": dense((d, G * D)),
             "wv": dense((d, G * D)), "wo": dense((q, d))}
        if sz["gate"]:
            p["wg"] = dense((d, q))
        return p

    def mlp(width, lead=()):
        return {"w_gate": dense(lead + (d, width)),
                "w_up": dense(lead + (d, width)),
                "w_down": dense(lead + (width, d))}

    def moe():
        p = {"router": dense((d, sz["E"])),
             "router_bias": dense((sz["E"],), 0.02),
             "experts": mlp(sz["eff"], (sz["held"][1] - sz["held"][0],))}
        if sz["shared"]:
            p["shared"] = mlp(sz["eff"] * sz["shared"])
        return p

    layers = [{"norm1": ones(d), "attn": attention(kind), "norm2": ones(d),
               "mlp": mlp(sz["ff"]) if m == "dense" else moe()}
              for kind, m in zip(sz["kinds"], sz["mlps"])]
    return {"embed": dense((sz["V"], d), 0.02), "layers": layers,
            "norm_f": ones(d), "lm_head": dense((d, sz["V"]))}


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


def inv_freq(rope, r):
    """``(inv_freq [r / 2] float32, factor)`` of one layer kind's rotation
    over the ``r`` channels that turn, from its entry of
    ``rope_parameters``: the plain one, or YaRN's placed by ``r``."""
    half = r // 2
    theta = float(rope["rope_theta"])
    plain = np.power(theta, -np.arange(half, dtype=np.float64) / half)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError("rotation %r" % rope["rope_type"])
    length = float(rope["original_max_position_embeddings"])

    def dim(turns):
        return r * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rope["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    scaled = (1.0 - ramp) * plain + ramp * plain / float(rope["factor"])
    return scaled.astype(np.float32), float(rope["attention_factor"])


def rotate(x, rope, r):
    """x [B, T, heads, D]: its first ``r`` channels turned by their
    positions 0..T - 1 (channel ``m`` with ``m + r / 2``), the rest as they
    come."""
    freq, factor = inv_freq(rope, r)
    T = x.shape[1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    part = x[..., :r]
    turned = jnp.concatenate([-part[..., r // 2:], part[..., :r // 2]],
                             axis=-1)
    part = (part * (jnp.cos(angle) * factor)
            + turned * (jnp.sin(angle) * factor))
    return jnp.concatenate([part, x[..., r:]], axis=-1)


def attention(x, p, sz, kind, q=lambda a: a, quant=None):
    """One attention layer over the block's normed input x [B, T, d].
    ``quant`` plants a fault: ``"window_left_out"``, ``"yarn_left_out"``,
    ``"gate_left_out"`` or ``"rotary_whole"`` (module docstring)."""
    B, T, _ = x.shape
    H, G, D = sz["H"][kind], sz["G"], sz["D"]
    turn = "swa" if quant == "yarn_left_out" else kind
    rope = sz["rope"][turn]
    r = D if quant == "rotary_whole" else sz["rotary"][turn]
    qh = rotate((q(x) @ q(p["wq"])).reshape(B, T, H, D), rope, r)
    kh = rotate((q(x) @ q(p["wk"])).reshape(B, T, G, D), rope, r)
    vh = (q(x) @ q(p["wv"])).reshape(B, T, G, D)
    # every query head its own copy of the key/value head it reads
    kh, vh = (jnp.repeat(t, H // G, axis=2) for t in (kh, vh))
    qh, kh, vh = q(qh), q(kh), q(vh)
    rows = ROW_BLOCK if T % ROW_BLOCK == 0 else T
    reach = sz["window"] if (kind == "swa"
                             and quant != "window_left_out") else T

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
    if sz["gate"] and quant != "gate_left_out":
        o = o * jax.nn.sigmoid(q(x) @ q(p["wg"]))
    return q(o) @ q(p["wo"])


def _expert(x, p, q):
    return q(jax.nn.silu(q(x) @ q(p["w_gate"])) * (q(x) @ q(p["w_up"]))) @ q(
        p["w_down"])


def route(x, p, sz):
    """Scores over ALL experts, the chosen ones and their weights:
    idx, w [..., top_k] (float32; never quantised)."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, idx = lax.top_k(s + lax.stop_gradient(p["router_bias"]), sz["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * sz["route_scale"]


def moe(x, p, sz, q=lambda a: a, count_shared=True, capacity=None):
    """The expert layer's part that the experts ``sz["held"]`` give, plus
    the shared expert: a loop over the held experts, each over every token
    under a mask. Returns (y, assignments per held expert). ``capacity``
    (the planted fault): assignments an expert takes, in token order."""
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

    y = jnp.zeros_like(x)
    if count_shared and "shared" in p:
        y = _expert(x, p["shared"], q)
    return lax.scan(one, y, (jnp.arange(lo, hi), p["experts"]))


def forward(params, tokens, sz, quant=None):
    """tokens [B, T] -> (logits [B, T, V] float32, routing counts
    [moe layers, held])."""
    q = lowprec.quantiser(None if quant in EXTRA_CONTROLS else quant)
    capacity = None
    if quant == "assignments_dropped":
        capacity = DROP_CAPACITY * tokens.size * sz["top_k"] / sz["E"]
    x = params["embed"][tokens]
    counts = []
    for lp, kind, m in zip(params["layers"], sz["kinds"], sz["mlps"]):

        @jax.checkpoint
        def block(x, lp, kind=kind, m=m):
            h = _rms_norm(x, lp["norm1"], sz["eps"])
            x = x + attention(h, lp["attn"], sz, kind, q, quant)
            h = _rms_norm(x, lp["norm2"], sz["eps"])
            if m == "dense":
                return x + _expert(h, lp["mlp"], q), None
            y, n = moe(h, lp["mlp"], sz, q,
                       count_shared=quant != "shared_left_out",
                       capacity=capacity)
            return x + y, n

        x, n = block(x, lp)
        if n is not None:
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
