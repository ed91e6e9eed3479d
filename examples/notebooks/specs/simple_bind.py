CELLS = [
("md", """
# `Symbol.simple_bind`: the executor without the estimator

The reference ships this walkthrough as
`example/notebooks/simple_bind.ipynb`: build a symbol with BatchNorm,
let `simple_bind` allocate every argument/gradient/aux array from shape
inference, initialize by writing into `arg_dict`, and run the training
loop yourself with a hand-written SGD update — no `FeedForward`, no
`Module`, no optimizer object.

Unlike `mx.model`, a single executor lives on exactly ONE device; the
multi-device story (executor groups, kvstore) is built on top of this
primitive.
"""),
("code", """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath("__file__")))))

import numpy as np
import mxnet_tpu as mx
mx.random.seed(11); np.random.seed(11)
"""),
("code", """
# mx.sym is the short alias for mx.symbol
data = mx.sym.Variable("data")
fc1  = mx.sym.FullyConnected(data=data, num_hidden=128, name="fc1")
bn1  = mx.sym.BatchNorm(data=fc1, name="bn1")
act1 = mx.sym.Activation(data=bn1, act_type="relu", name="relu1")
fc2  = mx.sym.FullyConnected(data=act1, num_hidden=10, name="fc2")
softmax = mx.sym.SoftmaxOutput(data=fc2, name="softmax")
softmax.list_arguments()
"""),
("md", """
## Bind

`simple_bind` runs shape inference from the shapes you pass, allocates
arg/grad/aux arrays on the chosen context, and returns the `Executor`.
`ctx=mx.cpu()` here; on a chip, `ctx=mx.tpu()` — the executor API is
identical.
"""),
("code", """
batch_size = 100
ctx = mx.cpu()
executor = softmax.simple_bind(ctx=ctx, data=(batch_size, 784),
                               softmax_label=(batch_size,))

args = executor.arg_dict          # name -> argument NDArray
grads = executor.grad_dict        # name -> gradient NDArray
aux_states = executor.aux_dict    # BatchNorm's moving mean/var live here
print(sorted(args), '\\n', sorted(aux_states))
"""),
("code", """
# initialize by mutating the bound arrays in place
args['fc1_weight'][:] = mx.random.uniform(-0.07, 0.07, args['fc1_weight'].shape)
args['fc2_weight'][:] = np.random.uniform(-0.07, 0.07, args['fc2_weight'].shape)  # equivalent
args['fc1_bias'][:] = 0.0
args['fc2_bias'][:] = 0.0
args['bn1_gamma'][:] = 1.0
args['bn1_beta'][:] = 0.0
"""),
("md", """
## A hand-written update rule

The update is just another in-place NDArray mutation — exactly what an
`Optimizer` does under the hood (and what a kvstore updater runs
server-side in distributed mode).
"""),
("code", """
def SGD(key, weight, grad, lr=0.1, grad_norm=batch_size):
    # key lets you customize the rule per parameter (lr mults, weight decay...)
    norm = 1.0 / grad_norm
    weight[:] -= lr * (grad * norm)

def Accuracy(label, pred_prob):
    pred = np.argmax(pred_prob, axis=1)
    return np.sum(label == pred) * 1.0 / label.shape[0]
"""),
("md", """
## Data and the loop

Forward with `is_train=True`, backward, apply `SGD` to every parameter
that is not an input — three lines per batch. The loss layer's backward
seeds the gradient chain itself (`SoftmaxOutput` is softmax + cross
entropy), so `backward()` takes no head gradient.
"""),
("code", """
train_iter = mx.io.MNISTIter(batch_size=batch_size, num_synthetic=4000,
                             seed=1, flat=True)
val_iter   = mx.io.MNISTIter(batch_size=batch_size, num_synthetic=1000,
                             seed=2, flat=True, shuffle=False)

num_round = 3
keys = softmax.list_arguments()
for r in range(num_round):
    train_iter.reset()
    train_acc = []
    for batch in train_iter:
        args['data'][:] = batch.data[0]
        args['softmax_label'][:] = batch.label[0]
        executor.forward(is_train=True)
        pred_prob = executor.outputs[0].asnumpy()
        executor.backward()
        for key in keys:
            if key in ('data', 'softmax_label'):
                continue
            SGD(key, args[key], grads[key])
        train_acc.append(Accuracy(batch.label[0].asnumpy(), pred_prob))
    print('round %d: train accuracy %.3f' % (r, np.mean(train_acc)))
"""),
("code", """
val_acc = []
val_iter.reset()
for batch in val_iter:
    args['data'][:] = batch.data[0]
    args['softmax_label'][:] = batch.label[0]
    executor.forward(is_train=False)   # inference mode: BN uses moving stats
    val_acc.append(Accuracy(batch.label[0].asnumpy(),
                            executor.outputs[0].asnumpy()))
print('validation accuracy: %.3f' % np.mean(val_acc))
assert np.mean(val_acc) > 0.9, np.mean(val_acc)
"""),
("md", """
## What BatchNorm left behind

Training-mode forwards updated the auxiliary moving-average states in
place — they are graph state, not parameters (no gradients flow into
them), and `is_train=False` above consumed them. This mutation-during-
forward discipline is the reference's aux-state contract
(`include/mxnet/operator.h` aux states; SURVEY §7 names it a hard part).
"""),
("code", """
mm = aux_states['bn1_moving_mean'].asnumpy()
mv = aux_states['bn1_moving_var'].asnumpy()
print('moving mean/var norms: %.3f / %.3f' % (
    np.abs(mm).mean(), np.abs(mv).mean()))
assert np.abs(mm).mean() > 1e-4      # forwards actually updated them
assert (mv > 0).all()
"""),
]
