CELLS = [
("md", """
# CIFAR-10 recipe

The reference ships this workflow as
`example/notebooks/cifar10-recipe.ipynb`: build the small-inception
CIFAR network out of factory functions, train it with `FeedForward`,
save/load the model two ways (pickle and checkpoint files), predict,
and extract an internal feature layer.

To keep the notebook self-contained and fast on CPU it trains on a
synthetic CIFAR-shaped task (class = colored quadrant pattern, 16x16x3)
through the same `NDArrayIter` path; point the iterators at packed
RecordIO files (`tools/im2rec.py` + `mx.io.ImageRecordIter`) for the
real dataset — nothing else changes. On a chip, set `ctx=mx.tpu()`;
for multi-device data parallelism, `ctx=[mx.tpu(i) for i in range(n)]`
— `FeedForward` splits each batch across the executor group and reduces
gradients through the kvstore exactly like the reference.
"""),
("code", """
import os, sys, pickle
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath("__file__")))))

import numpy as np
import mxnet_tpu as mx
import logging
logging.getLogger().setLevel(logging.INFO)
mx.random.seed(42); np.random.seed(42)
"""),
("code", """
# Basic Conv + BN + ReLU factory
def ConvFactory(data, num_filter, kernel, stride=(1,1), pad=(0, 0),
                act_type="relu"):
    conv = mx.symbol.Convolution(data=data, num_filter=num_filter,
                                 kernel=kernel, stride=stride, pad=pad)
    bn = mx.symbol.BatchNorm(data=conv)
    act = mx.symbol.Activation(data=bn, act_type=act_type)
    return act

# A simple downsampling factory: stride-2 conv next to a max pool
def DownsampleFactory(data, ch_3x3):
    conv = ConvFactory(data=data, kernel=(3, 3), stride=(2, 2),
                       num_filter=ch_3x3, pad=(1, 1))
    pool = mx.symbol.Pooling(data=data, kernel=(3, 3), stride=(2, 2),
                             pad=(1, 1), pool_type='max')
    return mx.symbol.Concat(conv, pool)

# A simple inception module: 1x1 tower next to a 3x3 tower
def SimpleFactory(data, ch_1x1, ch_3x3):
    conv1x1 = ConvFactory(data=data, kernel=(1, 1), pad=(0, 0),
                          num_filter=ch_1x1)
    conv3x3 = ConvFactory(data=data, kernel=(3, 3), pad=(1, 1),
                          num_filter=ch_3x3)
    return mx.symbol.Concat(conv1x1, conv3x3)
"""),
("code", """
# The recipe network, scaled to the notebook budget (the reference's
# full CIFAR body is the same composition with 3x the filters).
data = mx.symbol.Variable(name="data")
conv1 = ConvFactory(data=data, kernel=(3,3), pad=(1,1), num_filter=24)
in3a = SimpleFactory(conv1, 8, 8)
in3b = SimpleFactory(in3a, 8, 12)
in3c = DownsampleFactory(in3b, 20)
in4a = SimpleFactory(in3c, 16, 16)
in4b = DownsampleFactory(in4a, 24)
in5a = SimpleFactory(in4b, 24, 24)
pool = mx.symbol.Pooling(data=in5a, pool_type="avg", kernel=(4,4),
                         name="global_pool")
flatten = mx.symbol.Flatten(data=pool, name="flatten1")
fc = mx.symbol.FullyConnected(data=flatten, num_hidden=10, name="fc1")
softmax = mx.symbol.SoftmaxOutput(data=fc, name="loss")
mx.viz.print_summary(softmax, shape={"data": (1, 3, 16, 16),
                                     "loss_label": (1,)})
"""),
("md", """
## Data

A CIFAR-shaped synthetic task: each image carries a bright quadrant
patch whose (channel, position) combination defines one of 10 classes.
`NDArrayIter` is the in-memory iterator; the real recipe swaps in
`ImageRecordIter` over a `.rec` file with random crop/mirror
augmentation.
"""),
("code", """
def make_cifar_like(n, rng):
    x = rng.rand(n, 3, 16, 16).astype(np.float32) * 0.3
    y = rng.randint(0, 10, n).astype(np.float32)
    for i in range(n):
        cls = int(y[i])
        ch, q = cls % 3, cls % 4
        r0, c0 = (q // 2) * 8, (q % 2) * 8
        x[i, ch, r0:r0 + 8, c0:c0 + 8] += 0.5 + 0.1 * (cls // 4)
    return x, y

rng = np.random.RandomState(0)
X_train, y_train = make_cifar_like(1600, rng)
X_test, y_test = make_cifar_like(1000, rng)

batch_size = 64
train_iter = mx.io.NDArrayIter(X_train, y_train, batch_size=batch_size,
                               shuffle=True, label_name="loss_label")
test_iter = mx.io.NDArrayIter(X_test, y_test, batch_size=batch_size,
                              label_name="loss_label")
"""),
("md", """
## Train
"""),
("code", """
num_epoch = 4
model = mx.model.FeedForward(ctx=mx.cpu(), symbol=softmax,
                             num_epoch=num_epoch,
                             learning_rate=0.1, momentum=0.9, wd=0.00001,
                             initializer=mx.initializer.Xavier())
model.fit(X=train_iter, eval_data=test_iter, eval_metric="accuracy",
          batch_end_callback=mx.callback.Speedometer(batch_size, 16))
"""),
("md", """
## Save and load, two ways

Pickle serializes the whole estimator in-process; `save_checkpoint`
writes the reference's two-file format — `prefix-symbol.json` (the
graph) + `prefix-%04d.params` (binary NDArray map) — which every
binding and the predict API can read back.
"""),
("code", """
# 1. pickle
smodel = pickle.dumps(model)
model2 = pickle.loads(smodel)

# 2. checkpoint files (S3/HDFS URIs work through the stream layer)
prefix = "cifar10_notebook"
model.save(prefix)
model3 = mx.model.FeedForward.load(prefix, num_epoch, ctx=mx.cpu())
print(sorted(os.listdir('.')))
"""),
("code", """
prob = model3.predict(test_iter)
print('predict output:', prob.shape)

# score the restored model; all three copies agree batch-for-batch
acc3 = model3.score(test_iter)
acc2 = model2.score(test_iter)
print('restored accuracy: %.3f (pickle: %.3f)' % (acc3, acc2))
assert abs(acc3 - acc2) < 1e-6
assert acc3 > 0.9, acc3
for f in os.listdir('.'):
    if f.startswith(prefix):
        os.remove(f)
"""),
("md", """
## Predict internal feature maps

`get_internals` exposes every intermediate symbol; binding a new model
over the `global_pool` output with the SAME trained arguments turns the
classifier into a feature extractor (the standard transfer-learning
move — `predict-with-pretrained-model.ipynb` does this with a zoo
checkpoint).
"""),
("code", """
internals = softmax.get_internals()
print([n for n in internals.list_outputs() if 'pool' in n][-3:])
fea_symbol = internals["global_pool_output"]

feature_extractor = mx.model.FeedForward(
    ctx=mx.cpu(), symbol=fea_symbol, numpy_batch_size=batch_size,
    arg_params=model.arg_params, aux_params=model.aux_params,
    allow_extra_params=True)
global_pooling_feature = feature_extractor.predict(X_test[:256])
print('feature shape:', global_pooling_feature.shape)
assert global_pooling_feature.shape == (256, 48, 1, 1)  # in5a concat = 24+24
"""),
]
