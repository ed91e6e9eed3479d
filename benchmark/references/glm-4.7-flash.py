"""Plain reference for the ``glm-4.7-flash`` configuration.

GLM-4.7-Flash (``model_type glm4_moe_lite``; the Hugging Face ``config.json``
named in ``configs/glm-4.7-flash.json``): a pre-norm decoder with RMS norm,
an untied head, the same latent attention in every layer, a dense gated MLP in
layer 0 and sparse experts after it, and one multi-token-prediction module
trained with the model:

* **latent attention**: ``cq = rms(x Wq_a; g_q)`` (2048 -> 768, its own norm
  weight), ``q = cq Wq_b`` (768 -> 20 x 256), a head's 256 split into
  ``q_nope`` (192) and ``q_rot`` (64); ``[ckv, k_rot] = x Wkv_a`` (2048 -> 512
  + 64), ``[k_nope, v] = rms(ckv; g_kv) Wkv_b`` (512 -> 20 x (192 + 256));
  ``k_rot`` is ONE 64-wide row shared by the 20 heads. ``q_rot`` and ``k_rot``
  turn: ``inv_freq_m = theta ** (-2 m / 64)``, ``m = 0..31``, theta 1e6, cos
  and sin of ``pos * inv_freq`` laid twice side by side over the 64 channels,
  ``x' = x cos + [-x[32:], x[:32]] sin`` in float32 (channel ``m`` with ``m +
  32``: ``assumed.rope_pairing``). ``q = [q_nope, q_rot']``, ``k = [k_nope,
  k_rot']``, scores ``q.k / 16``, causal softmax, ``o = p v`` (20 x 256),
  ``o Wo`` (5120 -> 2048). Plain masked softmax in blocks of rows.
* **MoE** (every layer but the first): ``s = sigmoid(x Wg)`` over all 64
  experts, the 4 largest of ``s + b`` (``b`` a buffer no gradient reaches),
  weights ``1.8 s_i / sum_chosen(s)``, plus one shared expert; every expert
  is ``Wdown(SiLU(Wgate x) * Wup x)`` at width 1536. THIS CHIP'S SHARE: only
  the experts ``experts_held`` are here; the block gives their part of the
  sum and the shared expert, and that partial result goes on (the guide's
  cut, in the program and here alike). A loop over the held experts with a
  mask; held = all of them is the uncut layer (the share test).
* layer 0 has the dense MLP of the same form at width 10,240.
* **multi-token prediction** (DeepSeek-V3, arXiv:2412.19437 section 2.2): with
  ``h_i`` the main model's last hidden state after the final norm (what the
  head reads) and ``e_{i+1}`` the SHARED embedding of the next token, ``z_i =
  [rms(e_{i+1}; g_e), rms(h_i; g_h)] W_eh`` (4096 -> 2048), one more block of
  the MoE kind with its own weights, ``logits'_i = rms(z_i; g_s) lm_head`` (the
  SHARED head), which predicts token ``i + 2``. **Loss**: the mean of
  ``CE(logits_i, t_{i+1})`` over ``i = 0..T-1`` plus ``mtp_loss_weight`` times
  the mean of ``CE(logits'_i, t_{i+2})`` over ``i = 0..T-2``, float32.

Straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``. It imports nothing of ``mxnet_tpu``
and takes nothing the program has made: the weights are drawn here from the
seed and the driver hands the same draw to the program.

``quant``: ``None`` is the reference; ``"fp8"`` is the control: every operand
the configuration's ``precision`` states as bfloat16 (of every projection, of
the experts, the dense MLP and the head, of attention's q.k and p.v) is
rounded to float8_e4m3 by tensor (``lowprec.quantiser``); what it states as
float32 (the residual stream, norms, the rotation, router scores, softmax,
both losses, masters and optimizer state) stays float32. Four more names plant
this model's own faults in the float32 reference, for ``calibrate.py`` to read
them at the cell's size beside the control (``EXTRA_CONTROLS``):
``"rope_left_out"`` (the two 64-wide parts are not turned), ``"mtp_left_out"``
(the second loss's weight is 0), ``"mtp_unshifted"`` (the module embeds token
``i`` instead of ``i + 1``) and ``"assignments_dropped"`` (a capacity of
``DROP_CAPACITY`` of an expert's mean load; what overflows it, in token order,
is dropped).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import lowprec
import traffic

ADAM = dict(b1=0.9, b2=0.999, eps=1e-8)
#: rows of a block of the masked softmax
ROW_BLOCK = 256
#: faults ``calibrate.py`` reads besides the ``"fp8"`` control
EXTRA_CONTROLS = ("rope_left_out", "mtp_left_out", "mtp_unshifted",
                  "assignments_dropped")
DROP_CAPACITY = 0.75


def sizes(config):
    """The sizes this file computes with, from the configuration's file."""
    L = int(config["num_layers"])
    dense = int(config["first_k_dense_replace"])
    lo, hi = (int(i) for i in config["experts_held"])
    published = config.get("published", {})
    return dict(
        d=int(config["hidden_size"]), L=L, V=int(config["vocab_size"]),
        eps=float(config["rms_norm_eps"]),
        mlps=tuple("dense" if i < dense else "moe" for i in range(L)),
        H=int(config["num_attention_heads"]),
        rq=int(config["q_lora_rank"]), r=int(config["kv_lora_rank"]),
        dn=int(config["qk_nope_head_dim"]),
        dr=int(config["qk_rope_head_dim"]), dv=int(config["v_head_dim"]),
        theta=float(config["rope_theta"]),
        ff=int(config["intermediate_size"]),
        eff=int(config["moe_intermediate_size"]),
        E=int(published.get("n_routed_experts",
                            config["n_routed_experts"])),
        held=(lo, hi), top_k=int(config["num_experts_per_tok"]),
        shared=int(config["n_shared_experts"]),
        route_scale=float(config["routed_scaling_factor"]),
        renormalize=bool(config["norm_topk_prob"]),
        mtp=int(config["num_nextn_predict_layers"]),
        mtp_weight=float(config["assumed"]["mtp_loss_weight"]),
        # not the reference's (it rebuilds what it likes): what the
        # program is told to keep for its backward pass
        kept=tuple(config["assumed"]["kept"]))


# -- the configuration's own count of a step's work --------------------------------


def _forward_flops_per_token(sz, T):
    """Matrix products of one token's forward pass (2 a multiply-add), as
    the algorithm needs them: nothing recomputed, causal attention once,
    the routed experts at the assignments that land here in expectation,
    the head once a loss."""
    d, H, dq = sz["d"], sz["H"], sz["dn"] + sz["dr"]
    mla = 2 * d * sz["rq"] + 2 * sz["rq"] * H * dq \
        + 2 * d * (sz["r"] + sz["dr"]) \
        + 2 * sz["r"] * H * (sz["dn"] + sz["dv"]) + 2 * H * sz["dv"] * d \
        + H * (2 * dq + 2 * sz["dv"]) * (T + 1) / 2.0
    expert = 3 * 2 * d * sz["eff"]
    here = sz["top_k"] * (sz["held"][1] - sz["held"][0]) / float(sz["E"])
    moe = 2 * d * sz["E"] + (sz["shared"] + here) * expert
    dense = 3 * 2 * d * sz["ff"]
    total = 2 * d * sz["V"] + sum(
        mla + (dense if mlp == "dense" else moe) for mlp in sz["mlps"])
    # the module: eh_proj, one block of the MoE kind, the shared head again
    return total + sz["mtp"] * (2 * 2 * d * d + mla + moe + 2 * d * sz["V"])


def train_flops(config, mix):
    """Model FLOPs of one training step (forward and backward, nothing
    recomputed; the routed experts at their expected load here, top_k x
    held / published assignments a token): what ``mfu.train`` divides."""
    T = int(mix["seq_len"])
    return 3 * int(mix["batch"]) * T * _forward_flops_per_token(
        sizes(config), T)


def attention_work(config, mix):
    """(FLOPs, least bytes) of one step's softmax attention (the latent
    layers and the module's), forward and backward: causal pairs x (2 x 256
    + 2 x 256) forward, twice that backward; forward reads q, k, v and
    writes o, backward reads q, k, v, o, do and writes dq, dk, dv (2 bytes
    each, 20 heads of 256)."""
    sz = sizes(config)
    B, T = int(mix["batch"]), int(mix["seq_len"])
    dq, dv = sz["dn"] + sz["dr"], sz["dv"]
    layers = sz["L"] + sz["mtp"]
    pairs = T * (T + 1) / 2.0
    work = 3 * B * sz["H"] * pairs * (2 * dq + 2 * dv)
    nbytes = B * sz["H"] * T * 2 * ((2 * dq + 2 * dv) + (4 * dq + 4 * dv))
    return layers * work, layers * nbytes


# -- weights from the seed ---------------------------------------------------------


def _draw(sz, key):
    """The weights, float32, as the program holds them: a list of layers
    and the module under ``"mtp"``. Normal at 0.02 for the embedding,
    1/sqrt(fan-in) for every projection; norms 1; the router's
    score-correction bias normal at 0.02."""
    d = sz["d"]
    keys = iter(jax.random.split(key, 64 * (sz["L"] + 2)))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def mla():
        H, dq = sz["H"], sz["dn"] + sz["dr"]
        return {
            "wq_a": dense((d, sz["rq"])), "q_norm": ones(sz["rq"]),
            "wq_b": dense((sz["rq"], H * dq)),
            "wkva": dense((d, sz["r"] + sz["dr"])), "kv_norm": ones(sz["r"]),
            "wkvb": dense((sz["r"], H * (sz["dn"] + sz["dv"]))),
            "wo": dense((H * sz["dv"], d)),
        }

    def mlp(width, lead=()):
        return {"w_gate": dense(lead + (d, width)),
                "w_up": dense(lead + (d, width)),
                "w_down": dense(lead + (width, d))}

    def moe():
        n = sz["held"][1] - sz["held"][0]
        return {"router": dense((d, sz["E"])),
                "router_bias": dense((sz["E"],), 0.02),
                "experts": mlp(sz["eff"], (n,)),
                "shared": mlp(sz["eff"] * sz["shared"])}

    def block(m):
        return {"norm1": ones(d), "attn": mla(), "norm2": ones(d),
                "mlp": mlp(sz["ff"]) if m == "dense" else moe()}

    params = {"embed": dense((sz["V"], d), 0.02),
              "layers": [block(m) for m in sz["mlps"]],
              "norm_f": ones(d), "lm_head": dense((d, sz["V"]))}
    if sz["mtp"]:
        params["mtp"] = {"enorm": ones(d), "hnorm": ones(d),
                         "eh_proj": dense((2 * d, d)), "block": block("moe"),
                         "norm": ones(d)}
    return params


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


def rotate(x, theta):
    """x [B, T, heads, D] turned by its position: channel ``m`` pairs with
    ``m + D / 2`` and turns by ``pos * theta ** (-2 m / D)``."""
    T, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def mla_inputs(x, p, sz, q=lambda a: a, rope=True):
    """The heads the softmax takes, from the block's normed input x
    [B, T, d]: q, k [B, T, H, 256] and v [B, T, H, 256], float32."""
    B, T, _ = x.shape
    H, dn, dr, dv, r = sz["H"], sz["dn"], sz["dr"], sz["dv"], sz["r"]
    cq = _rms_norm(q(x) @ q(p["wq_a"]), p["q_norm"], sz["eps"])
    qh = (q(cq) @ q(p["wq_b"])).reshape(B, T, H, dn + dr)
    kva = q(x) @ q(p["wkva"])
    c, k_rot = kva[..., :r], kva[:, :, None, r:]
    kvb = (q(_rms_norm(c, p["kv_norm"], sz["eps"])) @ q(p["wkvb"])).reshape(
        B, T, H, dn + dv)
    q_rot = qh[..., dn:]
    if rope:
        q_rot, k_rot = rotate(q_rot, sz["theta"]), rotate(k_rot, sz["theta"])
    qh = jnp.concatenate([qh[..., :dn], q_rot], axis=-1)
    kh = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        k_rot, (B, T, H, dr))], axis=-1)
    return qh, kh, kvb[..., dn:]


def _mla(x, p, sz, q, rope=True):
    B, T, _ = x.shape
    H, dq, dv = sz["H"], sz["dn"] + sz["dr"], sz["dv"]
    qh, kh, vh = (q(t) for t in mla_inputs(x, p, sz, q, rope))
    rows = ROW_BLOCK if T % ROW_BLOCK == 0 else T
    scale = dq ** -0.5

    @jax.checkpoint
    def block(args):
        q_rows, row0 = args  # [B, rows, H, dq]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_rows, kh) * scale
        iq = row0 + jnp.arange(rows)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= iq, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", q(jax.nn.softmax(s, axis=-1)),
                          vh)

    q_blocks = jnp.moveaxis(qh.reshape(B, T // rows, rows, H, dq), 1, 0)
    o = lax.map(block, (q_blocks, jnp.arange(T // rows) * rows))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv)
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

    y = _expert(x, p["shared"], q) if count_shared else jnp.zeros_like(x)
    return lax.scan(one, y, (jnp.arange(lo, hi), p["experts"]))


def _faults(quant, tokens, sz):
    """(the quantiser, rotate?, the dropped-assignment capacity)."""
    q = lowprec.quantiser(None if quant in EXTRA_CONTROLS else quant)
    capacity = None
    if quant == "assignments_dropped":
        capacity = DROP_CAPACITY * tokens.size * sz["top_k"] / sz["E"]
    return q, quant != "rope_left_out", capacity


def hidden(params, tokens, sz, quant=None, next_tokens=None):
    """tokens [B, T] -> (h, z, counts): the main model's last hidden state
    after the final norm, the module's after its own (None without
    ``next_tokens`` [B, T], the token after each position) and the routing
    counts [moe blocks, held], the module's row last."""
    q, rope, capacity = _faults(quant, tokens, sz)
    counts = []

    def run(x, lp, mlp):
        @jax.checkpoint
        def block(x, lp):
            h = _rms_norm(x, lp["norm1"], sz["eps"])
            x = x + _mla(h, lp["attn"], sz, q, rope)
            h = _rms_norm(x, lp["norm2"], sz["eps"])
            if mlp == "dense":
                return x + _expert(h, lp["mlp"], q), None
            y, n = moe(h, lp["mlp"], sz, q, capacity=capacity)
            return x + y, n

        x, n = block(x, lp)
        if n is not None:
            counts.append(n)
        return x

    x = params["embed"][tokens]
    for lp, mlp in zip(params["layers"], sz["mlps"]):
        x = run(x, lp, mlp)
    h = _rms_norm(x, params["norm_f"], sz["eps"])
    z = None
    if next_tokens is not None:
        mp = params["mtp"]
        e = params["embed"][next_tokens]
        z = q(jnp.concatenate([_rms_norm(e, mp["enorm"], sz["eps"]),
                               _rms_norm(h, mp["hnorm"], sz["eps"])],
                              axis=-1)) @ q(mp["eh_proj"])
        z = _rms_norm(run(z, mp["block"], "moe"), mp["norm"], sz["eps"])
    return h, z, jnp.stack(counts)


def forward(params, tokens, sz, quant=None):
    """tokens [B, T] -> (the main model's logits [B, T, V] float32, routing
    counts [moe layers, held])."""
    q = _faults(quant, tokens, sz)[0]
    h, _, counts = hidden(params, tokens, sz, quant)
    return q(h) @ q(params["lm_head"]), counts


def mtp_logits(params, tokens, sz, quant=None):
    """tokens [B, T + 1] -> the module's logits [B, T, V]: position ``i``
    (from ``h_i`` and token ``i + 1``) predicts token ``i + 2``."""
    q = _faults(quant, tokens, sz)[0]
    _, z, _ = hidden(params, tokens[:, :-1], sz, quant, tokens[:, 1:])
    return q(z) @ q(params["lm_head"])


@jax.checkpoint
def _cross_entropy(h, head, targets):
    """Mean cross-entropy of ``h . head`` [B, n, V] against ``targets``."""
    logp = jax.nn.log_softmax(h @ head, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets[..., None], axis=-1)[..., 0])


def losses(params, tokens, sz, quant=None):
    """``(main, mtp)`` of ``tokens`` [B, T + 1], float32: the mean next-token
    cross-entropy over positions 0..T-1, and the module's mean
    cross-entropy against the token after next over positions 0..T-2."""
    q = _faults(quant, tokens, sz)[0]
    inputs = tokens[:, :-1]
    nexts = inputs if quant == "mtp_unshifted" else tokens[:, 1:]
    h, z, _ = hidden(params, inputs, sz, quant, nexts if sz["mtp"] else None)
    head = q(params["lm_head"])
    main = _cross_entropy(q(h), head, tokens[:, 1:])
    if not sz["mtp"]:
        return main, jnp.float32(0.0)
    return main, _cross_entropy(q(z[:, :-1]), head, tokens[:, 2:])


def loss_fn(params, tokens, sz, quant=None):
    main, mtp = losses(params, tokens, sz, quant)
    weight = 0.0 if quant == "mtp_left_out" else sz["mtp_weight"]
    return main + weight * mtp


def loss_and_grad(params, tokens, sz, quant=None):
    return jax.value_and_grad(loss_fn)(params, tokens, sz, quant)


def train_readings(config, seed, batches, lr, quant=None,
                   keep_positions=None):
    """The numbers a training cell compares, from the reference: the loss
    of each of the first steps, the norm of the first gradient and of the
    parameters' change after them, leaf by leaf. ``batches`` [steps, B,
    T + 1]. One jitted Adam step, called once a batch. ``keep_positions``
    plants the half-batch fault (the batch is one row: the first positions
    are kept and both means are over them)."""
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
        losses_, first = [], None
        for i, tokens in enumerate(np.asarray(batches)):
            params, mu, nu, loss, norms = step(
                params, mu, nu, jnp.asarray(tokens), jnp.float32(i + 1))
            losses_.append(float(loss))
            first = np.asarray(norms, np.float64) if first is None else first
        del mu, nu
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(
                params, make_params(config, seed))
    return dict(loss=np.asarray(losses_, np.float64), grad_norm=first,
                change_norm=np.asarray(change, np.float64))
