"""Model-zoo structural tests: the space-to-depth ResNet stem must be
arithmetically equivalent to the reference 7x7/s2/p3 stem under the
weight fold (models/resnet.py fold_stem_weights)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models.resnet import _s2d_stem, fold_stem_weights, get_resnet
from mxnet_tpu import symbol as sym


def test_s2d_stem_matches_conv7():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 224, 224).astype(np.float32)
    w7 = (rng.randn(64, 3, 7, 7) * 0.1).astype(np.float32)

    data = sym.Variable("data")
    ref = sym.Convolution(data=data, num_filter=64, kernel=(7, 7),
                          stride=(2, 2), pad=(3, 3), no_bias=True,
                          name="conv0_conv")
    exe = ref.simple_bind(mx.cpu(0), data=(2, 3, 224, 224), grad_req="null")
    exe.arg_dict["conv0_conv_weight"][:] = w7
    exe.arg_dict["data"][:] = x
    y_ref = exe.forward(is_train=False)[0].asnumpy()

    s2d = _s2d_stem(sym.Variable("data"), "conv0")
    exe2 = s2d.simple_bind(mx.cpu(0), data=(2, 3, 224, 224), grad_req="null")
    assert exe2.arg_dict["conv0_conv_weight"].shape == (64, 12, 4, 4)
    exe2.arg_dict["conv0_conv_weight"][:] = fold_stem_weights(w7)
    exe2.arg_dict["data"][:] = x
    y = exe2.forward(is_train=False)[0].asnumpy()

    assert y.shape == y_ref.shape == (2, 64, 112, 112)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_resnet_s2d_variant_builds_and_infers():
    s = get_resnet(num_classes=10, num_layers=50, stem="s2d")
    args, outs, _ = s.infer_shape(data=(4, 3, 224, 224),
                                  softmax_label=(4,))
    assert outs == [(4, 10)]
    names = s.list_arguments()
    i = names.index("conv0_conv_weight")
    assert args[i] == (64, 12, 4, 4)


def test_inception_bn_full_shapes():
    """Full Inception-BN (ref symbol_inception-bn.py get_symbol): the
    flagship baseline network behind BASELINE.md's ImageNet epoch
    times. Stage output shapes and the parameter census pin the
    composition; num_classes parameterizes the 21k full-ImageNet
    variant (symbol_inception-bn-full.py)."""
    import numpy as np

    net = mx.models.get_inception_bn(num_classes=1000)
    arg_shapes, out_shapes, aux_shapes = net.infer_shape(
        data=(2, 3, 224, 224), softmax_label=(2,))
    assert out_shapes == [(2, 1000)]
    # 2 aux states (moving mean/var) per BatchNorm
    n_bn = sum(1 for n in net.list_arguments() if n.endswith("_gamma"))
    assert len(aux_shapes) == 2 * n_bn
    n_params = sum(
        int(np.prod(s)) for nm, s in zip(net.list_arguments(), arg_shapes)
        if nm not in ("data", "softmax_label"))
    assert 11e6 < n_params < 12e6, n_params  # known ~11.3M parameter count
    # the 5b concat feeds global pool with 352+320+224+128 = 1024 ch
    internals = net.get_internals()
    _, pool_out, _ = internals["global_pool_output"].infer_shape(
        data=(2, 3, 224, 224))
    assert pool_out == [(2, 1024, 1, 1)]

    # 21k-class variant only widens the classifier
    net21k = mx.models.get_inception_bn(num_classes=21841)
    _, out21k, _ = net21k.infer_shape(data=(2, 3, 224, 224),
                                      softmax_label=(2,))
    assert out21k == [(2, 21841)]


def test_transformer_layer_norm_matches_numpy():
    """``_layer_norm`` against a float64 numpy reference: mean and biased
    variance over the last axis, eps inside the root, then scale and
    bias; the result keeps the input's dtype."""
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer as tf

    rng = np.random.RandomState(0)
    x = (rng.randn(2, 16, 32) * 3.0 + 1.5).astype(np.float32)
    p = {"scale": rng.rand(32).astype(np.float32) + 0.5,
         "bias": rng.randn(32).astype(np.float32)}
    x64 = x.astype(np.float64)
    mu = x64.mean(-1, keepdims=True)
    var = ((x64 - mu) ** 2).mean(-1, keepdims=True)
    want = (x64 - mu) / np.sqrt(var + 1e-5) * p["scale"] + p["bias"]

    got = tf._layer_norm(jnp.asarray(x), p)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # bf16 activations: statistics in float32, the output back in bf16
    got16 = tf._layer_norm(jnp.asarray(x, jnp.bfloat16), p)
    assert got16.dtype == jnp.bfloat16
    x16 = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                     np.float64)
    mu = x16.mean(-1, keepdims=True)
    var = ((x16 - mu) ** 2).mean(-1, keepdims=True)
    want16 = (x16 - mu) / np.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    np.testing.assert_allclose(
        np.asarray(got16.astype(jnp.float32)), want16, rtol=1e-2, atol=2e-2)


def test_transformer_loss_matches_numpy_cross_entropy():
    """``loss_fn`` is the mean next-token cross-entropy of ``forward``'s
    logits: a float64 numpy log-softmax over the vocabulary, the target
    one position ahead, the mean over every position of every row."""
    import jax

    from mxnet_tpu.models import transformer as tf

    cfg = tf.TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                               num_heads=2, d_ff=64, max_seq_len=32,
                               dtype="float32")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    f = tf.loss_fn(cfg)
    loss, grads = jax.value_and_grad(f)(params, {"tokens": tokens}, None)

    logits = np.asarray(tf.forward(params, tokens[:, :-1], cfg), np.float64)
    targets = np.asarray(tokens)[:, 1:]
    z = logits - logits.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp, targets[..., None], -1)[..., 0].mean()
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)
    # near ln(V) at init, and every parameter gets a finite gradient
    assert abs(want - np.log(cfg.vocab_size)) < 1.0
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
        assert float(np.abs(np.asarray(g)).sum()) > 0
