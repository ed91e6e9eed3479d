"""Hybrid decoder LMs: blocks that differ by layer.

``models/transformer.py`` writes one set of layer equations; this family is
told, layer by layer, which attention and which MLP a block has:

* ``attention[i]`` is ``"kda"`` (gated delta-rule linear attention with a
  per-channel decay, ``ops/kda.py``), ``"mla"`` (latent attention: keys
  and values rebuilt from a shared low-rank latent, a key part shared by
  the heads, the query straight from ``x`` or through a low-rank latent
  and norm of its own (``q_lora_rank``), softmax through
  ``ops.pallas_kernels.flash_attention``),
  ``"swa"`` or ``"full"`` (grouped-query softmax attention under rotary
  positions: ``num_heads`` query heads read ``num_kv_heads`` key/value
  heads of ``head_dim``; ``"swa"`` sees its last ``window`` keys and turns
  by the plain rotation, ``"full"`` sees every earlier key and turns by
  YaRN's; both through the same kernels);
* ``mlp[i]`` is ``"dense"`` (a gated SiLU MLP) or ``"moe"`` (one chip's
  share of a sparse expert layer, ``parallel.moe.moe_share_ffn``: the
  router (``router``: ``"sigmoid"`` with a score-correction bias, or
  ``"softmax"``) scores all ``num_experts``, this chip computes the experts
  ``experts_held = (lo, hi)`` and the shared expert if there is one,
  nothing is dropped).

Pre-norm residual blocks with RMS norm and an untied head. Position: the
``"kda"`` layers have no encoding (they carry position in their decay); an
``"mla"`` layer has none either with ``mla_rope_theta`` 0 (NoPE latent
attention beside linear attention) and otherwise turns the
``qk_rope_dim``-wide part of each query head and the one shared key part
by the plain rotation; the ``"swa"`` and ``"full"`` layers rotate q and k
whole (:func:`rope_inv_freq`). ``mtp_modules`` 1 adds a multi-token-
prediction module trained with the model (DeepSeek-V3's, arXiv:2412.19437
section 2.2): one more block over the main model's normed last hidden
state and the NEXT token's embedding, through the shared embedding and
head, whose cross-entropy on the token after next joins the loss at
``mtp_weight``. Published members: Kimi-Linear-48B-A3B (arXiv:2510.26692;
kda, NoPE mla, sigmoid router, a shared expert), Mellum2-12B-A2.5B (swa,
full, softmax router, no shared expert) and GLM-4.7-Flash (rotated mla
with a query latent in every layer, 256-wide keys and values, sigmoid
router, a shared expert, one prediction module);
``docs/how_to/hybrid_lm.md`` has the config keys.

Precision: parameters are float32 masters; every matrix product takes its
operands in ``cfg.dtype`` (bfloat16) and accumulates in float32; the
residual stream, norms, router scores, decays, the linear-attention state,
softmax statistics and the loss are float32.

Pure functions over a params pytree, reached like the transformer:
``parallel.make_train_step(hybrid_lm.loss_fn(cfg), optax.adam(lr),
has_aux=True)``; the step's aux is the routing counts [moe blocks, held]
(the layers', then the prediction module's where it has one).
Imported lazily (``from mxnet_tpu.models import hybrid_lm``): nothing of it
is loaded with the package.
"""
from __future__ import annotations

import dataclasses
import functools


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 32000
    d_model: int = 512
    attention: tuple = ("kda", "kda", "kda", "mla")
    mlp: tuple = ("dense", "moe", "moe", "moe")
    rms_eps: float = 1e-5
    # kda
    kda_heads: int = 4
    kda_head_dim: int = 128
    conv_kernel: int = 4
    # mla: ``q_lora_rank`` 0 takes the query straight from x, otherwise
    # through a latent of that width and its norm; ``mla_rope_theta`` 0
    # leaves the ``qk_rope_dim``-wide parts as they come (NoPE), otherwise
    # they turn by the plain rotation of that base
    num_heads: int = 4
    kv_lora_rank: int = 128
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64
    q_lora_rank: int = 0
    mla_rope_theta: float = 0.0
    # swa / full: ``num_heads`` query heads over ``num_kv_heads`` (0: as
    # many) key/value heads of ``head_dim``; the rotations' parameters
    num_kv_heads: int = 0
    head_dim: int = 64
    window: int = 1024
    rope_theta: float = 10000.0
    yarn_factor: float = 1.0
    yarn_original_length: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    # mlps
    d_ff: int = 2048
    moe_d_ff: int = 256
    num_experts: int = 16
    experts_per_token: int = 4
    experts_held: tuple = (0, 16)
    num_shared_experts: int = 1
    route_scale: float = 1.0
    renormalize: bool = True
    router: str = "sigmoid"  # or "softmax": parallel.moe.route_top_k
    # multi-token prediction: 0 or 1 module (a block of the last layer's
    # kinds) and the weight of its cross-entropy in the loss
    mtp_modules: int = 0
    mtp_weight: float = 0.3
    # what a half of a block keeps of its forward pass for its backward
    # pass, of the names ``ops/remat.py`` lists: None is ``KEPT`` (below);
    # the trainer's setting, sized to the state and the chip beside it
    kept: tuple = None
    dtype: str = "bfloat16"  # operands of the matrix products
    expert_axis: str = "expert"
    tensor_axis: str = "model"

    def __post_init__(self):
        if len(self.attention) != len(self.mlp):
            raise ValueError("attention and mlp name %d and %d layers"
                             % (len(self.attention), len(self.mlp)))
        bad = (set(self.attention) - {"kda", "mla", "swa", "full"}) | (
            set(self.mlp) - {"dense", "moe"})
        if bad:
            raise ValueError("unknown layer kinds %s" % sorted(bad))
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError("unknown router %r" % (self.router,))
        if self.num_heads % self.kv_heads or self.head_dim % 2:
            raise ValueError("%d query heads over %d key/value heads of %d"
                             % (self.num_heads, self.kv_heads, self.head_dim))
        if self.mla_rope_theta and self.qk_rope_dim % 2:
            raise ValueError("a rotated part of %d" % self.qk_rope_dim)
        if self.mtp_modules not in (0, 1):
            raise ValueError("%r prediction modules" % (self.mtp_modules,))
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError("experts_held %r of %d experts"
                             % (self.experts_held, self.num_experts))

    @property
    def num_layers(self):
        return len(self.attention)

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def moe_layers(self):
        return sum(m == "moe" for m in self.mlp)

    @property
    def moe_blocks(self):
        """Rows of the routing counts: the MoE layers and the prediction
        module's block where it is one."""
        return self.moe_layers + self.mtp_modules * (self.mlp[-1] == "moe")


def init_params(cfg: HybridConfig, key):
    """A params pytree, float32: normal at 0.02 for the embedding and
    1/sqrt(fan-in) for the projections, norms at 1, the convolution's taps
    at 1/2, ``A_log`` and ``dt_bias`` by the gated-delta-rule convention
    (decay rates uniform in [1, 16], time steps log-uniform in [0.001,
    0.1])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..parallel import moe

    d = cfg.d_model
    keys = iter(jax.random.split(
        key, 32 * (cfg.num_layers + 1 + cfg.mtp_modules)))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def kda():
        H, D = cfg.kda_heads, cfg.kda_head_dim
        w = H * D
        dt = jnp.exp(jax.random.uniform(
            next(keys), (w,), jnp.float32, np.log(1e-3), np.log(1e-1)))
        taps = (cfg.conv_kernel, w)
        return {
            "wq": dense((d, w)), "wk": dense((d, w)), "wv": dense((d, w)),
            "conv_q": dense(taps, 0.5), "conv_k": dense(taps, 0.5),
            "conv_v": dense(taps, 0.5),
            "f_a": dense((d, D)), "f_b": dense((D, w)),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (H,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "wb": dense((d, H)),
            "g_a": dense((d, D)), "g_b": dense((D, w)),
            "o_norm": jnp.ones((D,), jnp.float32),
            "wo": dense((w, d)),
        }

    def mla():
        H = cfg.num_heads
        dq, rq = H * (cfg.qk_nope_dim + cfg.qk_rope_dim), cfg.q_lora_rank
        query = {"wq": dense((d, dq))} if not rq else {
            "wq_a": dense((d, rq)), "q_norm": jnp.ones((rq,), jnp.float32),
            "wq_b": dense((rq, dq))}
        return {
            **query,
            "wkva": dense((d, cfg.kv_lora_rank + cfg.qk_rope_dim)),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
            "wkvb": dense((cfg.kv_lora_rank,
                           H * (cfg.qk_nope_dim + cfg.v_head_dim))),
            "wo": dense((H * cfg.v_head_dim, d)),
        }

    def gqa():
        q, kv = cfg.num_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        return {"wq": dense((d, q)), "wk": dense((d, kv)),
                "wv": dense((d, kv)), "wo": dense((q, d))}

    def mlp():
        return {"w_gate": dense((d, cfg.d_ff)), "w_up": dense((d, cfg.d_ff)),
                "w_down": dense((cfg.d_ff, d))}

    attn = {"kda": kda, "mla": mla, "swa": gqa, "full": gqa}

    def block(kind, m):
        return {
            "norm1": jnp.ones((d,), jnp.float32),
            "attn": attn[kind](),
            "norm2": jnp.ones((d,), jnp.float32),
            "mlp": mlp() if m == "dense" else moe.init_share_params(
                next(keys), cfg.num_experts, cfg.experts_held, d,
                cfg.moe_d_ff, cfg.num_shared_experts, score=cfg.router)}

    layers = [block(kind, m) for kind, m in zip(cfg.attention, cfg.mlp)]
    params = {"embed": dense((cfg.vocab_size, d), 0.02), "layers": layers,
              "norm_f": jnp.ones((d,), jnp.float32),
              "lm_head": dense((d, cfg.vocab_size))}
    if cfg.mtp_modules:  # no embedding and no head of its own
        params["mtp"] = {
            "enorm": jnp.ones((d,), jnp.float32),
            "hnorm": jnp.ones((d,), jnp.float32),
            "eh_proj": dense((2 * d, d)),
            "block": block(cfg.attention[-1], cfg.mlp[-1]),
            "norm": jnp.ones((d,), jnp.float32)}
    return params


def param_partition_specs(cfg: HybridConfig):
    """PartitionSpecs for a mesh with a tensor and an expert axis: the
    projections Megatron-style (columns in, rows out), the held experts on
    the expert axis, the vocabulary on the tensor axis."""
    from jax.sharding import PartitionSpec as P

    from ..parallel import moe

    t = cfg.tensor_axis
    col, row, rep = P(None, t), P(t, None), P()
    kda = {"wq": col, "wk": col, "wv": col, "conv_q": col, "conv_k": col,
           "conv_v": col, "f_a": rep, "f_b": col, "A_log": P(t),
           "dt_bias": P(t), "wb": col, "g_a": rep, "g_b": col,
           "o_norm": rep, "wo": row}
    mla = {"wkva": rep, "kv_norm": rep, "wkvb": col, "wo": row}
    mla.update({"wq_a": rep, "q_norm": rep, "wq_b": col} if cfg.q_lora_rank
               else {"wq": col})
    gqa = {"wq": col, "wk": col, "wv": col, "wo": row}
    attn = {"kda": kda, "mla": mla, "swa": gqa, "full": gqa}
    dense = {"w_gate": col, "w_up": col, "w_down": row}
    experts = moe.share_partition_specs(bool(cfg.num_shared_experts),
                                        cfg.expert_axis, cfg.router)
    def block(kind, m):
        return {"norm1": rep, "attn": dict(attn[kind]), "norm2": rep,
                "mlp": dict(dense if m == "dense" else experts)}

    specs = {"embed": row, "norm_f": rep, "lm_head": col, "layers": [
        block(kind, m) for kind, m in zip(cfg.attention, cfg.mlp)]}
    if cfg.mtp_modules:
        specs["mtp"] = {"enorm": rep, "hnorm": rep, "eh_proj": rep,
                        "block": block(cfg.attention[-1], cfg.mlp[-1]),
                        "norm": rep}
    return specs


# -- the layers --------------------------------------------------------------------


def _mm(x, w, dtype):
    import jax.numpy as jnp

    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    from jax import lax

    with jax.named_scope("norm"):
        return x * lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _short_conv(x, taps):
    """Depthwise causal convolution over time: x [B, T, C], taps [n, C]."""
    import jax.numpy as jnp

    n, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * taps[j] for j in range(n))


def kda_layer(x, p, cfg: HybridConfig):
    """x [B, T, d] (normed, float32) -> the KDA layer's output [B, T, d]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops.kda import kda_attention

    B, T, _ = x.shape
    H, D = cfg.kda_heads, cfg.kda_head_dim
    mm = functools.partial(_mm, dtype=cfg.dtype)

    def heads(w, taps):  # [B, T, H * D], a head's channels side by side
        return jax.nn.silu(_short_conv(mm(x, w), taps))

    # which head a channel belongs to, [H * D, H]: a head's sum of squares
    # and its way back to the channels are float32 products with it, so
    # that q and k stay laid out as the projections left them (a sum over
    # the last axis of [B, T, H, D] makes XLA re-tile the tensor twice)
    with jax.named_scope("kda"):
        member = (jnp.arange(H * D)[:, None] // D == jnp.arange(H)).astype(
            jnp.float32)
    hi = lax.Precision.HIGHEST

    def unit(t):
        scale = lax.rsqrt(jnp.dot(t * t, member, precision=hi) + 1e-6)
        return t * jnp.dot(scale, member.T, precision=hi)

    def split(t):
        return t.reshape(B, T, H, D)

    with jax.named_scope("kda"):
        q = split(unit(heads(p["wq"], p["conv_q"])) * D ** -0.5)
        k = split(unit(heads(p["wk"], p["conv_k"])))
        v = split(heads(p["wv"], p["conv_v"]))
        g = split(jnp.repeat(-jnp.exp(p["A_log"]), D) * jax.nn.softplus(
            mm(mm(x, p["f_a"]), p["f_b"]) + p["dt_bias"]))
        beta = jax.nn.sigmoid(mm(x, p["wb"]))
        o = kda_attention(q, k, v, g, beta, dtype=cfg.dtype)
        gate = jax.nn.sigmoid(mm(mm(x, p["g_a"]), p["g_b"]))
        o = _rms_norm(o, p["o_norm"], cfg.rms_eps).reshape(B, T, H * D) * gate
        return mm(o, p["wo"])


def mla_layer(x, p, cfg: HybridConfig):
    """x [B, T, d] (normed, float32) -> the latent-attention layer's
    output. The query comes straight from ``x`` (``wq``) or, with
    ``q_lora_rank``, through its own latent and norm (``wq_a``, ``q_norm``,
    ``wq_b``). With ``mla_rope_theta`` 0 nothing turns: the
    ``qk_rope_dim``-wide key part is shared by the heads as it comes
    (NoPE). Otherwise that part of every query head and the ONE shared key
    row turn by the plain rotation in float32 (``_rotate``'s pairing:
    channel ``m`` with ``m + qk_rope_dim / 2``), the key row before it is
    broadcast to the heads."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas_kernels import flash_attention

    B, T, _ = x.shape
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    dtype = jnp.dtype(cfg.dtype)
    mm = functools.partial(_mm, dtype=dtype)
    with jax.named_scope("mla"):
        if cfg.q_lora_rank:
            q = mm(_rms_norm(mm(x, p["wq_a"]), p["q_norm"], cfg.rms_eps),
                   p["wq_b"])
        else:
            q = mm(x, p["wq"])
        q = q.reshape(B, T, H, dn + dr)
        kva = mm(x, p["wkva"])
        kvb = mm(_rms_norm(kva[..., :r], p["kv_norm"], cfg.rms_eps),
                 p["wkvb"]).reshape(B, T, H, dn + dv)
        k_nope, k_rot = kvb[..., :dn], kva[:, :, None, r:]
        if cfg.mla_rope_theta:
            with jax.named_scope("rope"):
                cos, sin = _rope_table(
                    T, _plain_inv_freq(cfg.mla_rope_theta, dr))
                q = jnp.concatenate(
                    [q[..., :dn], _rotate(q[..., dn:], cos, sin)], axis=-1)
                k_rot = _rotate(k_rot, cos, sin)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rot, (B, T, H, dr))], axis=-1)

        def heads(t):
            return t.astype(dtype).transpose(0, 2, 1, 3)

        o = flash_attention(heads(q), heads(k), heads(kvb[..., dn:]),
                            causal=True, scale=(dn + dr) ** -0.5)
        return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * dv), p["wo"])


def _plain_inv_freq(theta, width):
    """The plain rotation's frequencies over ``width`` channels, float64:
    ``theta ** (-2 m / width)``, ``m = 0 .. width / 2 - 1``."""
    import numpy as np

    half = width // 2
    return theta ** (-np.arange(half, dtype=np.float64) / half)


def _rope_table(T, inv_freq, factor=1.0):
    """cos, sin [T, 2 x len(inv_freq)] float32 of positions 0 .. T - 1, the
    two halves side by side as :func:`_rotate` reads them, times
    ``factor``."""
    import jax.numpy as jnp

    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def rope_inv_freq(cfg: HybridConfig, kind):
    """``(inv_freq [head_dim / 2] float32, factor)`` of a layer kind's
    rotation: channel ``m`` and ``m + head_dim / 2`` turn by ``pos *
    inv_freq[m]``, and cos and sin are multiplied by ``factor``.

    ``"swa"``: the plain rotation, ``theta ** (-2 m / head_dim)``, factor 1.
    ``"full"``: YaRN (arXiv:2309.00071) as the published configurations'
    library computes it, static at every length: channels that turn more
    than ``beta_fast`` times over the original length keep their frequency,
    those that turn less than ``beta_slow`` times have it divided by
    ``yarn_factor``, a linear ramp between the two; both cos and sin carry
    ``yarn_attention_factor`` (so the scores carry its square)."""
    import math

    import numpy as np

    half = cfg.head_dim // 2
    plain = _plain_inv_freq(cfg.rope_theta, cfg.head_dim)
    if kind == "swa" or cfg.yarn_factor == 1.0:
        return plain.astype(np.float32), 1.0

    def turns_at(turns):  # the channel that turns ``turns`` times
        return cfg.head_dim * math.log(cfg.yarn_original_length / (
            turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(turns_at(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.yarn_beta_slow)), cfg.head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = plain * (1.0 - ramp) + plain / cfg.yarn_factor * ramp
    return inv_freq.astype(np.float32), float(cfg.yarn_attention_factor)


def _rotate(x, cos, sin):
    """x [B, T, H, D] float32 turned by cos, sin [T, D] (the two halves of
    D side by side): ``x cos + [-x2, x1] sin``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def gqa_layer(x, p, cfg: HybridConfig, kind):
    """x [B, T, d] (normed, float32) -> grouped-query softmax attention
    under rotary positions: ``kind`` ``"swa"`` (the last ``cfg.window``
    keys, the plain rotation) or ``"full"`` (every earlier key, YaRN). q
    and k are projected with ``cfg.dtype`` operands into float32, rotated
    in float32 and cast once for the kernels; ``num_heads / kv_heads``
    query heads read one key/value head, unrepeated."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas_kernels import flash_attention

    B, T, _ = x.shape
    H, G, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)
    mm = functools.partial(_mm, dtype=dtype)
    with jax.named_scope("attn." + kind):
        q = mm(x, p["wq"]).reshape(B, T, H, D)
        k = mm(x, p["wk"]).reshape(B, T, G, D)
        v = mm(x, p["wv"]).reshape(B, T, G, D)
        with jax.named_scope("rope"):
            cos, sin = _rope_table(T, *rope_inv_freq(cfg, kind))
            q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)

        def heads(t):
            return t.astype(dtype).transpose(0, 2, 1, 3)

        o = flash_attention(heads(q), heads(k), heads(v), causal=True,
                            scale=D ** -0.5,
                            window=cfg.window if kind == "swa" else None)
        return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * D), p["wo"])


def _attention_half(x, norm, p, kind, cfg):
    import jax

    h = _rms_norm(x, norm, cfg.rms_eps)
    if kind in ("swa", "full"):
        y = gqa_layer(h, p, cfg, kind)
    else:
        y = (kda_layer if kind == "kda" else mla_layer)(h, p, cfg)
    with jax.named_scope("residual"):
        return x + y


def _mlp_half(x, norm, p, mlp, cfg):
    import jax

    from ..parallel import moe

    h = _rms_norm(x, norm, cfg.rms_eps)
    if mlp == "dense":
        with jax.named_scope("mlp.dense"):
            y, counts = moe.gated_mlp(h, p, cfg.dtype), None
    else:
        B, T, d = h.shape
        y, counts = moe.moe_share_ffn(
            p, h.reshape(B * T, d), cfg.experts_per_token, cfg.experts_held,
            cfg.route_scale, cfg.renormalize, cfg.dtype, cfg.router)
        y = y.reshape(B, T, d)
    with jax.named_scope("residual"):
        return x + y, counts


#: what ``forward`` keeps of a half's forward pass for its backward pass, by
#: the names ``ops/remat.py`` lists, unless ``cfg.kept`` names fewer;
#: everything else in a half is rebuilt
KEPT = ("flash", "kda_chunk", "moe_sort", "moe_hidden")


def _run(params, tokens, cfg: HybridConfig, next_tokens=None):
    """The blocks over ``tokens`` [B, T]: ``(h, z, counts)``, the main
    model's normed last hidden state [B, T, d] float32 (what the head
    reads), the prediction module's (None without ``next_tokens``) and the
    routing counts, a list of one row [held experts] a MoE block.

    ``next_tokens`` [B, T], the token after each position, runs the
    multi-token-prediction module over the same pass: ``z = [rms(Emb(next);
    enorm), rms(h; hnorm)] eh_proj``, one more block (the same two
    checkpointed halves as every layer), ``rms(z; norm)``."""
    import jax
    import jax.numpy as jnp

    from .. import telemetry as _tel
    from ..ops import remat

    kept = KEPT if cfg.kept is None else cfg.kept
    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    offered = dict(remat.OFFERED)
    counts = []

    def block(x, lp, kind, mlp):
        x = jax.checkpoint(
            functools.partial(_attention_half, kind=kind, cfg=cfg),
            policy=policy)(x, lp["norm1"], lp["attn"])
        x, n = jax.checkpoint(
            functools.partial(_mlp_half, mlp=mlp, cfg=cfg),
            policy=policy)(x, lp["norm2"], lp["mlp"])
        if n is not None:
            counts.append(n)
        return x

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for lp, kind, mlp in zip(params["layers"], cfg.attention, cfg.mlp):
        x = block(x, lp, kind, mlp)
    h = _rms_norm(x, params["norm_f"], cfg.rms_eps)
    z = None
    if next_tokens is not None:
        with jax.named_scope("mtp"):
            mp = params["mtp"]
            with jax.named_scope("embed"):
                e = jnp.take(params["embed"], next_tokens, axis=0).astype(
                    jnp.float32)
            z = _mm(jnp.concatenate(
                [_rms_norm(e, mp["enorm"], cfg.rms_eps),
                 _rms_norm(h, mp["hnorm"], cfg.rms_eps)], axis=-1),
                mp["eh_proj"], cfg.dtype)
            z = block(z, mp["block"], cfg.attention[-1], cfg.mlp[-1])
            z = _rms_norm(z, mp["norm"], cfg.rms_eps)
    if _tel.ENABLED:  # on the host, at every trace: the module's share too
        for name in KEPT:
            _tel.gauge("remat.saved_bytes.%s" % name).set(
                remat.OFFERED.get(name, 0) - offered.get(name, 0)
                if name in kept else 0)
    return h, z, counts


def forward(params, tokens, cfg: HybridConfig):
    """tokens [B, T] int32 -> (logits [B, T, vocab] float32, routing
    counts [moe layers, held experts] int32): the main model, without its
    prediction module.

    Each half of a block (attention, MLP) is checkpointed: the backward
    pass gets the half's input and rebuilds the rest, one half at a time,
    EXCEPT what ``KEPT`` names, which costs more to rebuild than to hold (a
    kernel's forward would run a second time only to give its residuals
    back). Kept per token of B x T, with the kernels on: an MLA layer's
    flash output and log-sum-exp, ``H (2 dv + 32)`` bytes (9 KB at 32 heads
    of 128); a KDA layer's in-chunk results, ``H (8 D + 6 C + 4 D / C)``
    bytes at chunks of ``C = 64`` (45 KB at 32 heads of 128); a sorted
    expert layer's bucket rows and its gate and up products, ``4 + 8
    moe_d_ff`` bytes a row (two rows a token at 8 of 256 experts held: 16
    KB). The benchmark's five layers at B x T = 8192 keep 256 KB a token,
    2.10 GB (telemetry: ``remat.saved_bytes.<name>``); q, k, v, the gates,
    every projection, the state's pass and the experts' down product are
    rebuilt from the half's input as before.

    A swa or full layer keeps ``H (2 D + 32)`` bytes a token like MLA's.
    ``moe_hidden`` goes by the bucket's rows, not by what lands: at 16 of 64
    experts held the bucket is 65,536 rows for 8,192 tokens, everything
    that could land, and four layers' gate and up products are 1.88 GB of
    mostly empty rows where Kimi-Linear's four are 0.54 GB. It is kept all
    the same: the Mellum2 step then holds 5.23 GB while it runs (3.70 GB
    with the two products rebuilt) beside 7.14 GB of state, inside the chip
    (AOT, PR 34). GLM-4.7-Flash's six blocks (the prediction module's is
    one; :func:`losses`) keep 20 heads' ``2 x 256 + 32`` bytes a token of
    ``flash``, 0.53 GB, and at 8 of 64 experts held under top 4 a bucket of
    32,768 rows a block, 2.01 GB of ``moe_hidden`` over five: the step
    holds 6.01 GB while it runs beside 8.77 GB live between steps, with
    each of its two losses' logits rebuilt and not kept: 14.78 GB, over the
    14.5e9 the cells are held to (my chip runs, PR 36), where Kimi-Linear's
    step is 14.06 and Mellum2's 12.47. What differs is the state beside
    the step (11.31 GB of parameters, gradients and moments against 9.64
    and 9.52), which follows the optimizer and the chip, and ``forward``
    sees neither: so what is kept is the trainer's setting, ``cfg.kept``
    (None: ``KEPT``), and that cell's driver names ``("flash",
    "moe_sort")``: ten ``moe_gmm`` calls and the bucket's gather are
    rebuilt a step."""
    import jax

    x, _, counts = _run(params, tokens, cfg)
    counts = _stack_counts(counts, cfg)
    with jax.named_scope("head"):
        return _mm(x, params["lm_head"], cfg.dtype), counts


def _stack_counts(counts, cfg):
    import jax
    import jax.numpy as jnp

    lo, hi = cfg.experts_held
    with jax.named_scope("moe.route"):
        return jnp.stack(counts) if counts else jnp.zeros((0, hi - lo),
                                                          jnp.int32)


def _head_loss(h, head, targets, weights, dtype):
    """The weighted mean cross-entropy of ``rms-normed h . head`` against
    ``targets`` [B, T], float32: the head product, its log-softmax and the
    pick, for ``jax.checkpoint`` to wrap (a model with two losses over one
    head holds one pair of [B, T, vocab] float32 tensors at a time)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("head"):
        logits = _mm(h, head, dtype)
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weights) / jnp.sum(weights)


def losses(params, batch, cfg: HybridConfig):
    """``(main, mtp, counts)`` of batch = dict(tokens=[B, T + 1] int32)
    with a prediction module: the main model's mean cross-entropy of
    position ``i`` against token ``i + 1`` over ``i = 0 .. T - 1``; the
    module's of position ``i`` (the main model's ``h_i`` and the embedding
    of token ``i + 1``) against token ``i + 2`` over ``i = 0 .. T - 2``
    (the module runs over all T positions, so that the kernels see T; the
    last, whose target does not exist, carries weight 0); the routing
    counts [moe blocks, held], the module's row last. Both through the
    shared embedding and head, whose gradients add. Each head product and
    cross-entropy sits under its own ``jax.checkpoint``: the backward pass
    rebuilds one [B, T, vocab] product and one pair of such float32
    tensors is alive at a time, not two."""
    import jax
    import jax.numpy as jnp

    tokens = batch["tokens"]
    B, T = tokens.shape[0], tokens.shape[1] - 1
    with jax.named_scope("embed"):  # the batch's slicing
        inputs, next_tokens = tokens[:, :-1], tokens[:, 1:]
    h, z, counts = _run(params, inputs, cfg, next_tokens)
    head_loss = jax.checkpoint(
        functools.partial(_head_loss, dtype=cfg.dtype))
    with jax.named_scope("loss"):
        targets, weights = tokens[:, 1:], jnp.ones((B, T), jnp.float32)
    main = head_loss(h, params["lm_head"], targets, weights)
    with jax.named_scope("mtp"):
        with jax.named_scope("loss"):
            after_next = jnp.pad(tokens[:, 2:], ((0, 0), (0, 1)))
            weights = jnp.broadcast_to(
                (jnp.arange(T) < T - 1).astype(jnp.float32), (B, T))
        mtp = head_loss(z, params["lm_head"], after_next, weights)
    return main, mtp, _stack_counts(counts, cfg)


def loss_fn(cfg: HybridConfig):
    """Next-token cross-entropy closure for ``parallel.make_train_step(...,
    has_aux=True)``: batch = dict(tokens=[B, T + 1] int32) -> (loss, routing
    counts [moe blocks, held experts]). With a prediction module the loss
    is ``main + cfg.mtp_weight * mtp`` of :func:`losses`."""
    import jax
    import jax.numpy as jnp

    def f(params, batch, rng):
        del rng
        if cfg.mtp_modules:
            main, mtp, counts = losses(params, batch, cfg)
            with jax.named_scope("loss"):
                return main + cfg.mtp_weight * mtp, counts
        tokens = batch["tokens"]
        with jax.named_scope("embed"):  # the batch's slicing
            inputs = tokens[:, :-1]
        logits, counts = forward(params, inputs, cfg)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, tokens[:, 1:, None], axis=-1)[..., 0]
            return jnp.mean(nll), counts

    return f


def record_losses(main, mtp):
    """Telemetry of the two cross-entropies read back on the host (floats,
    from :func:`losses`): the gauges ``loss.main`` and ``loss.mtp``."""
    from .. import telemetry as _tel

    if _tel.ENABLED:
        _tel.gauge("loss.main").set(float(main))
        _tel.gauge("loss.mtp").set(float(mtp))


def record_routing(counts, tokens, cfg: HybridConfig):
    """Telemetry of routing counts read back on the host (``counts``
    [steps, moe layers, held experts], ``tokens`` routed a step): counters
    ``moe.assignments_total`` / ``moe.assignments_held_total`` and the gauge
    ``moe.held_load_max_over_mean`` (the busiest held expert's assignments
    over the mean). Returns that ratio, or None with nothing counted."""
    import numpy as np

    from .. import telemetry as _tel

    counts = np.asarray(counts, np.float64)
    if counts.size == 0 or counts.sum() == 0:
        return None
    per_expert = counts.reshape(-1, counts.shape[-1]).sum(axis=0)
    ratio = float(per_expert.max() / per_expert.mean())
    if _tel.ENABLED:
        steps = counts.shape[0]
        _tel.counter("moe.assignments_total").inc(
            int(steps * tokens * cfg.experts_per_token * cfg.moe_blocks))
        _tel.counter("moe.assignments_held_total").inc(int(counts.sum()))
        _tel.gauge("moe.held_load_max_over_mean").set(ratio)
    return ratio
