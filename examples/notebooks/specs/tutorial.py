CELLS = [
("md", """
# MXNet-TPU tutorial and handwritten digit recognition

The framework tour in notebook form (the reference ships this workflow as
`example/notebooks/tutorial.ipynb`): define a multilayer perceptron as a
`Symbol`, train it on MNIST-shaped data with `FeedForward`, evaluate,
peek inside training with `Monitor`, drop down to the raw
`simple_bind` executor loop, and finish with a custom operator written
in numpy.

Everything runs unchanged on CPU (`JAX_PLATFORMS=cpu`) or a TPU chip —
`mx.cpu()` / `mx.tpu()` is the only switch.
"""),
("code", """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath("__file__")))))

import numpy as np
import mxnet_tpu as mx
import logging
logging.getLogger().setLevel(logging.INFO)
mx.random.seed(7); np.random.seed(7)
"""),
("md", """
## Network definition

Variables are placeholders for input arrays; each layer symbol consumes
the one before it. Nothing is computed yet — a `Symbol` is only a graph
description.
"""),
("code", """
# The input placeholder.
data = mx.symbol.Variable('data')
# A fully connected layer computes Y = XW' + b.
fc1  = mx.symbol.FullyConnected(data=data, name='fc1', num_hidden=128)
act1 = mx.symbol.Activation(data=fc1, name='relu1', act_type="relu")
fc2  = mx.symbol.FullyConnected(data=act1, name='fc2', num_hidden=64)
act2 = mx.symbol.Activation(data=fc2, name='relu2', act_type="relu")
fc3  = mx.symbol.FullyConnected(data=act2, name='fc3', num_hidden=10)
# Softmax + cross-entropy loss against the label.
mlp  = mx.symbol.SoftmaxOutput(data=fc3, name='softmax')
mlp.list_arguments()
"""),
("code", """
# Layer-by-layer summary with output shapes and parameter counts.
mx.viz.print_summary(mlp, shape={"data": (100, 784)})
"""),
("md", """
## Data loading

`MNISTIter` reads the idx-format files when present and otherwise
generates a deterministic synthetic digit set with the same shapes and
statistics — this notebook stays self-contained. `flat=True` yields
`(batch, 784)` rows for the MLP.
"""),
("code", """
batch_size = 100
train_iter = mx.io.MNISTIter(batch_size=batch_size, num_synthetic=4000,
                             seed=1, flat=True)
test_iter  = mx.io.MNISTIter(batch_size=batch_size, num_synthetic=1000,
                             seed=2, flat=True, shuffle=False)
train_iter.provide_data, train_iter.provide_label
"""),
("md", """
## Training

`FeedForward` is the estimator facade: it initializes parameters, binds
the symbol into a fused train step (forward + backward + SGD in one XLA
program) and runs the epochs. `Speedometer` logs samples/sec — the
headline metric of every baseline table.
"""),
("code", """
model = mx.model.FeedForward(
    ctx=mx.cpu(),          # swap for mx.tpu() on a chip — nothing else changes
    symbol=mlp,
    num_epoch=10,
    learning_rate=0.1, momentum=0.9, wd=0.00001,
    initializer=mx.initializer.Xavier())
model.fit(X=train_iter, eval_data=test_iter,
          batch_end_callback=mx.callback.Speedometer(batch_size, 20))
"""),
("md", """
## Evaluation

`predict` returns softmax rows for a whole iterator; `score` runs an
`EvalMetric` over it.
"""),
("code", """
import matplotlib
matplotlib.use("Agg")
import matplotlib.pyplot as plt
%matplotlib inline

test_iter.reset()
batch = next(iter(test_iter))
img = np.asarray(batch.data[0].asnumpy()[0]).reshape(28, 28)
plt.imshow((img * 255).astype(np.uint8), cmap='Greys_r'); plt.show()
prob = model.predict(batch.data[0].asnumpy()[:1])[0]
print('predicted digit:', prob.argmax())
"""),
("code", """
acc = model.score(test_iter)
print('Accuracy: %.1f%%' % (acc * 100))
assert acc > 0.9, acc  # synthetic digits are separable; the MLP must learn them
"""),
("md", """
## Debugging with Monitor

`Monitor` taps every op output matching a pattern and computes a stat
tensor (L2 norm by default here) without stopping training — the
executor runs each op eagerly while a monitor is installed so every
intermediate is visible (ref: `graph_executor.cc` disables bulk-exec
segments under a monitor).
"""),
("code", """
def norm_stat(d):
    return mx.nd.norm(d) / np.sqrt(d.size)

records = []
class Tap(logging.Handler):
    def emit(self, rec):
        records.append(rec.getMessage())
tap = Tap(); logging.getLogger().addHandler(tap)

mon = mx.monitor.Monitor(interval=20, stat_func=norm_stat,
                         pattern='fc2.*')   # only tap fc2's tensors
mon_model = mx.model.FeedForward(ctx=mx.cpu(), symbol=mlp, num_epoch=1,
                                 learning_rate=0.1,
                                 initializer=mx.initializer.Xavier())
mon_model.fit(X=train_iter, monitor=mon)
logging.getLogger().removeHandler(tap)

fc2_lines = [r for r in records if 'fc2' in r]
print('\\n'.join(fc2_lines[:4]))
assert fc2_lines  # the tap fired and saw only the requested tensors
assert not [r for r in records if 'Batch:' in r and 'fc1' in r]
"""),
("md", """
## Under the hood: the executor loop

`simple_bind` allocates all argument/gradient arrays from shape
inference and returns an `Executor`. `FeedForward` is nothing but this
loop plus bookkeeping: forward, backward, apply an update rule to every
parameter, repeat.
"""),
("code", """
executor = mlp.simple_bind(ctx=mx.cpu(), data=(batch_size, 784),
                           softmax_label=(batch_size,))
args, grads = executor.arg_dict, executor.grad_dict
for name in mlp.list_arguments():
    if name.endswith('weight'):
        args[name][:] = mx.random.uniform(-0.07, 0.07, args[name].shape)
    elif name.endswith('bias'):
        args[name][:] = 0.0

lr = 0.1
train_iter.reset()
for epoch in range(3):
    train_iter.reset()
    for b in train_iter:
        args['data'][:] = b.data[0]
        args['softmax_label'][:] = b.label[0]
        executor.forward(is_train=True)
        executor.backward()
        for name in mlp.list_arguments():
            if name not in ('data', 'softmax_label'):
                args[name][:] -= lr / batch_size * grads[name]

correct = total = 0
test_iter.reset()
for b in test_iter:
    args['data'][:] = b.data[0]
    args['softmax_label'][:] = b.label[0]
    executor.forward(is_train=False)
    pred = executor.outputs[0].asnumpy().argmax(axis=1)
    correct += (pred == b.label[0].asnumpy()).sum(); total += pred.size
print('manual-loop accuracy: %.3f' % (correct / total))
assert correct / total > 0.9
"""),
("md", """
## New operators, in numpy

`NumpyOp` runs user python inside the graph — forward and backward are
plain numpy methods, shape inference included (ref:
`python/mxnet/operator.py` NumpyOp; the `Custom` op escape hatch).
The reference tutorial defines softmax this way; swapping it for the
built-in `SoftmaxOutput` changes nothing else in the network.
"""),
("code", """
class NumpySoftmax(mx.operator.NumpyOp):
    def __init__(self):
        super(NumpySoftmax, self).__init__(need_top_grad=False)
    def list_arguments(self):
        return ['data', 'label']
    def list_outputs(self):
        return ['output']
    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape]
    def forward(self, in_data, out_data):
        x = in_data[0]
        y = out_data[0]
        y[:] = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
    def backward(self, out_grad, in_data, out_data, in_grad):
        l = in_data[1].astype(int)
        y = out_data[0]
        dx = in_grad[0]
        dx[:] = y
        dx[np.arange(l.shape[0]), l] -= 1.0

mlp_np = NumpySoftmax()(data=fc3, name='softmax')
np_model = mx.model.FeedForward(ctx=mx.cpu(), symbol=mlp_np, num_epoch=4,
                                learning_rate=0.1, momentum=0.9,
                                initializer=mx.initializer.Xavier())
np_model.fit(X=train_iter)
acc_np = np_model.score(test_iter)
print('NumpySoftmax accuracy: %.3f' % acc_np)
assert acc_np > 0.9, acc_np
"""),
("md", """
That is the whole stack: `Symbol` graphs, iterators, the `FeedForward`
estimator, monitoring, the raw executor, and python-defined operators —
each later notebook in this directory goes deeper on one of these.
"""),
]
